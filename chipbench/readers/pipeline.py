"""Readers of the `pipeline` block that every summary carries
(telemetry/pipeline.py PipelineStats.snapshot): last summary of the window
minus the one that opened it."""

from __future__ import annotations


def starved_share(run) -> float | None:
    t = run.tap
    opened = [s for _t, b, s in t.summaries if b == t.first_batch - 1]
    inside = t.window_summaries()
    if not opened or not inside:
        return None
    a, b = opened[0].pipeline, inside[-1][2].pipeline
    if not a or not b:
        return None
    starved = b["starved"] - a["starved"]
    ticks = starved + b["saturated"] - a["saturated"]
    return 100.0 * starved / ticks if ticks > 0 else None
