"""Readers of what sharded ingest adds to the program's accounting
(operators/tpusketch.py under `shard-ingest`): the sharding-only stages of
the turn and the counters of rounds, filler lanes and events per lane.
Registry deltas over the part of the window the counters cover, as
readers/turn.py takes them. A program without these names (one chip, or a
commit before they existed) has nothing to read: every reader returns None
and the metric is left out.
"""

from __future__ import annotations

from readers import turn

ROUNDS = "ig_tpusketch_shard_rounds_total"
FILLERS = "ig_tpusketch_shard_filler_lanes_total"
LANE_EVENTS = "ig_tpusketch_shard_lane_events_total"


def _deltas(run, name: str) -> list[float]:
    """A counter family's delta per label set; empty without the family."""
    start = run.tap.snap_start
    return [v - start.get(k, 0.0) for k, v in run.tap.snap_end.items()
            if k.startswith(name + "{")]


def _opened(run, stages: list[str]) -> bool:
    """Whether the program has timed any of `stages`: a stage only a
    sharded run opens has no counter child until its first nanosecond."""
    return any(f'{turn.SECONDS}{{stage="{s}"}}' in run.tap.snap_end
               for s in stages)


def stage_ms_per_turn(run, stages: list[str]) -> float | None:
    """readers/turn.py's reading, for stages only a sharded run opens."""
    if not _opened(run, stages):
        return None
    return turn.stage_ms_per_turn(run, stages)


def stage_ms_per(run, stage: str, counter: str) -> float | None:
    """The same, per occurrence counted by `counter` (a harvest)."""
    if not _opened(run, [stage]):
        return None
    return turn.stage_ms_per(run, stage, counter)


def round_fill_share(run) -> float | None:
    """Share of the dispatched rounds' lanes that carried a batch:
    100 x (1 - filler lanes / (lanes x rounds))."""
    rounds = sum(_deltas(run, ROUNDS))
    lanes = len(_deltas(run, LANE_EVENTS))
    if rounds <= 0 or not lanes:
        return None
    return 100.0 * (1.0 - sum(_deltas(run, FILLERS)) / (lanes * rounds))


def lane_skew(run) -> float | None:
    """Events parked on the busiest lane over the mean lane's."""
    lanes = _deltas(run, LANE_EVENTS)
    if sum(lanes) <= 0:
        return None
    return max(lanes) * len(lanes) / sum(lanes)
