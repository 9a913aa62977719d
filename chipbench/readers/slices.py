"""Reader of the history window's slice accounting
(`ig_history_slices_total{gadget,decision}`, counted at each seal's finish
from the window's store): slices a sealed window kept against slices it
dropped over `history-max-slices`. Registry deltas over the part of the
window the counters cover. A program without the counter (a commit before
it existed, or history off) has nothing to read: the reader returns None
and the metric is left out.
"""

from __future__ import annotations

SLICES = "ig_history_slices_total"


def _delta(run, decision: str) -> float:
    """The family's delta over the label sets of one decision."""
    start, end = run.tap.snap_start, run.tap.snap_end
    return sum(v - start.get(k, 0.0) for k, v in end.items()
               if k.startswith(SLICES + "{") and f'decision="{decision}"' in k)


def dropped_share(run) -> float | None:
    """100 x dropped / (admitted + dropped) over the window's seals."""
    dropped = _delta(run, "dropped")
    asked = _delta(run, "admitted") + dropped
    return 100.0 * dropped / asked if asked > 0 else None
