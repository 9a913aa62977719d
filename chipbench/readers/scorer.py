"""Reader of the anomaly scorer's step (operators/tpusketch.py under
`anomaly true`, models/autoencoder.py `anomaly_step`): its share of its
roofline. The stages and the counted part only such a run opens are read
through readers/shard.py, whose readings are None where a stage has no
counter child. A program without the step's name or the `anomaly` block
(the scorer off, or a commit before they existed) has nothing to read: the
reader returns None and the metric is left out.

The work of one step is counted here from the layer sizes alone, whatever
the program does to compute it:

- operations: a forward pass over `rows` rows is rows x W multiply-adds,
  2 operations each, W = the four weight matrices' entries
  (d x h + h x z + z x h + h x d). The step is one forward and one backward
  pass for the gradient (the backward pass costs two forward passes, less
  the first layer's input gradient, which nothing needs) and one forward
  pass more for the scores of the updated weights.
- bytes: the f32 parameters and Adam's two moments read once and written
  once (6 x 4 B x P, P = W + the biases), the `rows x d` f32 counts read
  once, the `rows` f32 scores written once. Gradients, activations and
  the bf16 copies of the weights need never leave the chip, so they are
  not counted: an implementation that spills them reads a lower share,
  and no implementation can read over 100%.
"""

from __future__ import annotations

import work
from readers import trace


def step_work(rows: int, dims: list[int]) -> dict:
    """Operations and bytes of one `anomaly_step` on `rows` rows of an
    autoencoder d -> h -> z -> h -> d (`dims` = [d, h, z])."""
    d, h, z = dims
    weights = d * h + h * z + z * h + h * d
    params = weights + h + z + h + d
    forward = 2 * rows * weights
    return {"ops": 4 * forward - 2 * rows * d * h,
            "bytes": 6 * 4 * params + 4 * rows * d + 4 * rows}


def _slots(run) -> int | None:
    """Rows the scorer's step ran on: `pipeline["anomaly"]["slots"]` of
    the window's last summary."""
    for _at, _b, s in reversed(run.tap.window_summaries()):
        block = (s.pipeline or {}).get("anomaly")
        if block:
            return int(block["slots"])
    return None


def step_roofline(run, programs: list[str], dims: list[int]) -> float | None:
    """The least time the chip needs for one step's operations and bytes
    (peaks.json) over the step's device time in the trace."""
    ms = trace.program_ms_per_run(run, programs)
    rows = _slots(run)
    if ms is None or rows is None:
        return None
    peak = work.peaks(run.device_kind)
    w = step_work(rows, dims)
    least = max(w["bytes"] / peak["hbm_bytes_per_s"],
                w["ops"] / peak["flops_per_s"])
    return 100.0 * least / (1e-3 * ms)
