"""Readers of the history window's own accounting (operators/tpusketch.py,
ops/window.py): the routing stage of the turn, the window program on the
device, the seals' delay behind their window's end and the late events of
event-time windows. Registry deltas over the part of the window the
counters cover, as readers/turn.py takes them. A program without these
names (a commit before they existed, or history off) has nothing to read:
every reader returns None and the metric is left out.
"""

from __future__ import annotations

import work
from readers import registry, trace, turn

ROUTE = "tpusketch_window_route"
STEPS = "ig_tpusketch_steps_total"
STEP_ROWS = "ig_tpusketch_step_rows_total"
EVENTS = "ig_tpusketch_events_total"
LATE = "ig_history_late_events_total"


def _delta(run, family: str) -> float:
    return (registry.total(run.tap.snap_end, family)
            - registry.total(run.tap.snap_start, family))


def _has(run, family: str) -> bool:
    return any(k == family or k.startswith(family + "{")
               for k in run.tap.snap_end)


def route_ms_per_turn(run) -> float | None:
    """Loop-thread milliseconds a turn spends routing events to windows."""
    if f'{turn.SECONDS}{{stage="{ROUTE}"}}' not in run.tap.snap_end:
        return None
    return turn.stage_ms_per_turn(run, [ROUTE])


def window_work(rows: int, lanes: int, narrow_lanes: int, depth: int
                ) -> dict:
    """Bytes moved and operations for one window step of `rows` rows,
    whatever implements it: the uint32 lanes it reads (the distinct key
    columns and the weights) and the one-byte age lanes read once, one read
    and one write of 4 B for each of the row's depth + 2 counters (its
    window's count-min rows, entropy bucket and HLL register), and of its
    window's count. ops: one hash per counter touched, and the count."""
    touched = rows * (depth + 2)
    return {"bytes": (4 * lanes + narrow_lanes) * rows + 8 * touched
            + 8 * rows,
            "ops": touched + rows}


def planes_roofline(run, programs: list[str]) -> float | None:
    """The least time of the window program's work over its device time
    per run, at the rows the steps ran at (pad rows included)."""
    ms = trace.program_ms_per_run(run, programs)
    steps = _delta(run, STEPS)
    if ms is None or steps <= 0:
        return None
    rows = _delta(run, STEP_ROWS) / steps
    # event-time windows stage one narrow age lane; their runs alone
    # carry the late-event counter
    w = window_work(round(rows), run.staged_lanes, int(_has(run, LATE)),
                    run.geometry["depth"])
    least, _binds = work.least_seconds(w, work.peaks(run.device_kind))
    return 100.0 * least / (ms / 1e3)


def late_share(run) -> float | None:
    """100 x late events / events absorbed, over the window."""
    if not _has(run, LATE):
        return None
    events = _delta(run, EVENTS)
    return 100.0 * _delta(run, LATE) / events if events > 0 else None
