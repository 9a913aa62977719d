"""Readers of the program's turn accounting (telemetry/pipeline.py TurnClock):
the stages of the batch turn, timed by the loop thread itself.

Stage seconds and counts are registry deltas between the window's opening
and the moment the counters closed (readers/registry.py), over the turns
published in between. The turn's wall, the stager's stall and the longest
turns come from the `pipeline` block of the summaries: the one that opened
the window and the last one the counters cover (readers/pipeline.py). A
program without the accounting has none of these names, every reader
returns None and the metric is left out.
"""

from __future__ import annotations

from readers.registry import total

SECONDS = "ig_pipeline_turn_seconds_total"
TURNS = "ig_pipeline_turns_total"


def _delta(run, name: str) -> float:
    return total(run.tap.snap_end, name) - total(run.tap.snap_start, name)


def _stage_seconds(run, stages: list[str]) -> float:
    a, b = run.tap.snap_start, run.tap.snap_end
    keys = [f'{SECONDS}{{stage="{s}"}}' for s in stages]
    return sum(b.get(k, 0.0) - a.get(k, 0.0) for k in keys)


def stage_ms_per_turn(run, stages: list[str]) -> float | None:
    """Loop-thread milliseconds a turn spends in the named stages."""
    turns = _delta(run, TURNS)
    return 1e3 * _stage_seconds(run, stages) / turns if turns > 0 else None


def stage_ms_per(run, stage: str, counter: str) -> float | None:
    """Milliseconds of a stage per occurrence counted by `counter` (a seal,
    a harvest)."""
    if _delta(run, TURNS) <= 0:
        return None
    n = _delta(run, counter)
    return 1e3 * _stage_seconds(run, [stage]) / n if n > 0 else None


def count(run, counter: str) -> float | None:
    """A counter's delta over the window; None where the program has no
    such counter (0 is a reading)."""
    snap = run.tap.snap_end
    if not any(k == counter or k.startswith(counter + "{") for k in snap):
        return None
    return _delta(run, counter)


def _blocks(run) -> tuple[dict, dict] | None:
    """The `pipeline` blocks of the summary that opened the window and of
    the last one inside the part of it the counters cover."""
    t = run.tap
    opened = [s for _t, b, s in t.summaries if b == t.first_batch - 1]
    inside = [s for at, _b, s in t.window_summaries() if at <= t.counters_end]
    if not opened or not inside:
        return None
    a, b = opened[0].pipeline, inside[-1].pipeline
    return (a, b) if a and b else None


def _turns(run) -> tuple[dict, dict] | None:
    """The run totals of the turn accounting in those two blocks."""
    blocks = _blocks(run)
    if blocks is None or "turn" not in blocks[0] or "turn" not in blocks[1]:
        return None
    return blocks[0]["turn"], blocks[1]["turn"]


def turn_ms(run) -> float | None:
    """Wall milliseconds of a turn: the program's own sum of turn walls
    over its count of turns."""
    pair = _turns(run)
    if pair is None:
        return None
    a, b = pair
    turns = b["turns"] - a["turns"]
    return 1e3 * (b["wall_s"] - a["wall_s"]) / turns if turns > 0 else None


def accounted_share(run) -> float | None:
    """Share of the turns' wall that the stages cover."""
    pair = _turns(run)
    if pair is None:
        return None
    a, b = pair
    wall = b["wall_s"] - a["wall_s"]
    staged = sum(b["stages"].values()) - sum(a["stages"].values())
    return 100.0 * staged / wall if wall > 0 else None


def stall_ms_per_tick(run) -> float | None:
    """The stager's blocking wait on the slot it is about to reuse, per
    tick (starved or saturated). The counters are older than the turn
    accounting, so a program without it is read too."""
    blocks = _blocks(run)
    if blocks is None:
        return None
    a, b = blocks
    ticks = (b["starved"] + b["saturated"]) - (a["starved"] + a["saturated"])
    return 1e3 * (b["stall_s"] - a["stall_s"]) / ticks if ticks > 0 else None


def _longest(run) -> dict | None:
    """The longest kept turn that started inside the part of the window the
    counters cover. Every summary carries the four longest turns of the run
    so far, so the window's longest is looked for in all of them."""
    t = run.tap
    best = None
    for _at, _b, s in t.summaries:
        for row in (s.pipeline or {}).get("slow_turns", ()):
            if (t.window_start <= row["start"] <= t.counters_end
                    and (best is None or row["wall_s"] > best["wall_s"])):
                best = row
    return best


def longest_ms(run) -> float | None:
    row = _longest(run)
    return None if row is None else 1e3 * row["wall_s"]


def longest_cpu_share(run) -> float | None:
    """Loop-thread CPU time of the longest turn over its wall: near 100 the
    thread was working, far under it the thread waited or was descheduled."""
    row = _longest(run)
    if row is None or row["wall_s"] <= 0:
        return None
    return 100.0 * row["cpu_s"] / row["wall_s"]
