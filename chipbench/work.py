"""The algorithmic work of one sketch update, from geometry alone.

One batch of B events is staged as L uint32 lanes and folded into the
bundle: every event touches `depth` count-min counters, one entropy bucket
and one HyperLogLog register, and the batch re-ranks the top-k table once.
That is what ANY implementation of the step must do, so the roofline share
reads the same work for the fused kernel and for the scatter step. What an
implementation adds of its own (the fused kernel's one-hot compares, a
scatter's sort) is not algorithmic work and is not counted.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).with_name("peaks.json")


def update_work(batch: int, lanes: int, depth: int, topk: int) -> dict:
    """Bytes moved and operations for one update of `batch` events.

    bytes: the staged lanes read once (4 B x lanes x batch), one read and
    one write of 4 B for each of the batch x (depth + 2) counters touched
    (count-min rows, entropy bucket, HLL register), and the top-k table
    (key + count, 8 B a slot) read and written once.
    ops: one hash per counter touched.
    """
    touched = batch * (depth + 2)
    return {"bytes": 4 * lanes * batch + 8 * touched + 16 * topk,
            "ops": touched}


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}: add it with its source")
    return table[device_kind]


def least_seconds(work: dict, peak: dict) -> tuple[float, str]:
    """The least time the chip needs for `work`, and which bound binds."""
    by_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    by_ops = work["ops"] / peak["int8_ops_per_s"]
    return (by_bytes, "bytes") if by_bytes >= by_ops else (by_ops, "ops")
