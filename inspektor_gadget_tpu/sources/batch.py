"""EventBatch: the struct-of-arrays unit of transport.

Fixed-capacity columnar batches with an explicit valid-count and cumulative
loss counters — the contract every hop preserves (capture ring → bridge →
sketch plane → agent stream), reproducing the reference's end-to-end loss
accounting (perf LostSamples → tracer warn events → stream EventLost →
seq-gap checks; SURVEY §5 failure detection).
"""

from __future__ import annotations

import dataclasses

import numpy as np

# Canonical wire columns (matches native/events.h Event layout).
BATCH_COLUMNS: dict[str, np.dtype] = {
    "ts": np.dtype(np.uint64),
    "key_hash": np.dtype(np.uint64),
    "aux1": np.dtype(np.uint64),
    "aux2": np.dtype(np.uint64),
    "mntns": np.dtype(np.uint64),
    "pid": np.dtype(np.uint32),
    "ppid": np.dtype(np.uint32),
    "uid": np.dtype(np.uint32),
    "kind": np.dtype(np.uint32),
}


@dataclasses.dataclass
class EventBatch:
    cols: dict[str, np.ndarray]
    count: int                 # valid rows (rest is padding)
    seq: int = 0               # first event's sequence number
    drops: int = 0             # cumulative upstream drops at pop time
    forgiven: int = 0          # cumulative events the producer skipped
    comm: np.ndarray | None = None  # (capacity, 8) uint8 display prefixes
    # pipeline-health watermarks (epoch seconds; 0.0 = unstamped): one
    # stamp per BATCH, never per event — host lag = pop_ts − oldest_ts,
    # device lag = dispatch − pop_ts (telemetry/pipeline.py)
    pop_ts: float = 0.0        # wall clock when the host popped the batch
    oldest_ts: float = 0.0     # oldest event timestamp in the batch

    @property
    def capacity(self) -> int:
        return len(next(iter(self.cols.values())))

    def mask(self) -> np.ndarray:
        m = np.zeros(self.capacity, dtype=bool)
        m[: self.count] = True
        return m

    @classmethod
    def alloc(cls, capacity: int, with_comm: bool = True) -> "EventBatch":
        cols = {n: np.zeros(capacity, dtype=dt) for n, dt in BATCH_COLUMNS.items()}
        comm = np.zeros((capacity, 8), dtype=np.uint8) if with_comm else None
        return cls(cols=cols, count=0, comm=comm)

    def comm_str(self, i: int) -> str:
        if self.comm is None:
            return ""
        raw = bytes(self.comm[i])
        return raw.split(b"\0", 1)[0].decode("utf-8", "replace")


# Lane order of the folded SoA block — rows 0..3 of one (lanes >= 3,
# capacity) uint32 array per batch: a single pinned allocation carries
# all lanes, so one pool slot == one batch and the native exporter fills
# them with one call. The values lane (row 3, per-event magnitude for
# the DDSketch quantile plane) is optional: 3-lane blocks simply don't
# carry it and `FoldedBatch.values` reports None. Blocks may carry extra
# rows (tpusketch's staging pool allocates 4+ lanes so the same pool
# serves the EventBatch path); a block's shape must match the pool it
# came from or put() drops it.
FOLDED_LANES = ("keys", "weights", "mntns", "values")


@dataclasses.dataclass
class FoldedBatch:
    """Pre-folded struct-of-arrays batch — the sketch plane's native unit.

    Produced by `ig_source_pop_folded` (native/api.cc) draining a capture
    ring directly into caller-owned uint32 lanes: `keys` is the xor-folded
    key_hash (the sketch key width, no Python decode/fold pass), `weights`
    the per-event weight (1 today; reserved for capture-side aggregation),
    `mntns` the xor-folded mount-ns id (exact for real ns inodes < 2^32 —
    the late-enrichment display key). Blocks popped through
    `ig_source_pop_folded2` additionally fill `values` (row 3): the
    per-event magnitude — latency ns or byte count, saturate-cast from
    the kind's aux1, 0 for kinds without one — feeding the DDSketch
    quantile plane. The lanes are the leading rows of ONE pinned
    (lanes >= 3, capacity) block owned by a PinnedBufferPool slot;
    consumers must release the block back to the SAME pool once the H2D
    transfer completes.
    """

    lanes: "np.ndarray"        # (>=3, capacity) uint32 — pool-owned block
    count: int                 # valid rows (rest is padding)
    seq: int = 0               # first event's sequence number
    drops: int = 0             # cumulative upstream drops at pop time
    # True only when the producer actually FILLED row 3 (pop_folded2):
    # legacy 4-lane pool blocks keep row 3 as scratch, so shape alone
    # cannot prove the lane holds real magnitudes
    has_values: bool = False
    # pipeline-health watermarks (epoch seconds; 0.0 = unstamped). The
    # folded lanes carry no per-event timestamp column, so oldest_ts is
    # the previous pop's wall clock — a documented UPPER-bound watermark
    # (no event in this batch can predate the last drain that emptied
    # the ring region it came from)
    pop_ts: float = 0.0
    oldest_ts: float = 0.0

    @property
    def capacity(self) -> int:
        return self.lanes.shape[1]

    @property
    def keys(self) -> "np.ndarray":
        return self.lanes[0]

    @property
    def weights(self) -> "np.ndarray":
        return self.lanes[1]

    @property
    def mntns(self) -> "np.ndarray":
        return self.lanes[2]

    @property
    def values(self) -> "np.ndarray | None":
        """Per-event magnitude lane (uint32 latency ns / bytes), or None
        for batches popped without the value lane."""
        if self.has_values and self.lanes.shape[0] >= 4:
            return self.lanes[3]
        return None
