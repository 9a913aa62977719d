"""Sliding-window sketches — time-decayed analytics on device.

The reference's interval machinery keeps only the latest snapshot per node
with a TTL (pkg/snapshotcombiner: entries age out after N ticks without
refresh), and top gadgets reset their stats map every interval. The
TPU-native generalization: a ring of S epoch slots per sketch; updates land
in the current slot, a query sums the most recent k slots ("heavy hitters
over the last k intervals"), and advancing the epoch zeroes the oldest slot
— all static shapes, one jitted step, mergeable across nodes slot-wise.

This is also the long-sequence story: an unbounded event sequence becomes a
rotating window of bounded per-epoch summaries, the streaming analogue of
blockwise/context-parallel attention windows.

`WindowPlanes` is the history window's device state around that ring: beside
each open window's count-min slot, a HyperLogLog register plane, an entropy
histogram and an exact event count. Every event carries the AGE of its window
(0 = the newest open window, 1 = the one before, ...; an age past the open
planes is a late event, which lands in none): a processing-time window gives
one scalar age for the whole batch, an event-time window one per event.
"""

from __future__ import annotations

import flax.struct
import jax
import jax.numpy as jnp

from .hashing import multiply_shift, row_hashes
from .hll import hll_index_rank


@flax.struct.dataclass
class WindowedCMS:
    slots: jnp.ndarray   # (S, depth, width) int32 — epoch ring of CM tables
    epoch: jnp.ndarray   # () int32 — current slot index
    log2_width: int = flax.struct.field(pytree_node=False)

    @property
    def n_slots(self) -> int:
        return self.slots.shape[0]

    @property
    def depth(self) -> int:
        return self.slots.shape[1]


def wcms_init(n_slots: int = 8, depth: int = 4, log2_width: int = 14) -> WindowedCMS:
    return WindowedCMS(
        slots=jnp.zeros((n_slots, depth, 1 << log2_width), jnp.int32),
        epoch=jnp.zeros((), jnp.int32),
        log2_width=log2_width,
    )


def wcms_update(state: WindowedCMS, keys: jnp.ndarray,
                weights: jnp.ndarray | None = None,
                age: jnp.ndarray | int = 0) -> WindowedCMS:
    """Scatter-add the batch into the slot `age` epochs behind the current
    one (a scalar, or one age per key)."""
    if weights is None:
        weights = jnp.ones(keys.shape, jnp.int32)
    idx = row_hashes(keys, state.depth, state.log2_width)  # (depth, n)
    rows = jnp.broadcast_to(
        jnp.arange(state.depth, dtype=jnp.int32)[:, None], idx.shape)
    slot = jnp.broadcast_to(
        (state.epoch - jnp.asarray(age, jnp.int32)) % state.n_slots,
        idx.shape)
    slots = state.slots.at[
        slot.reshape(-1), rows.reshape(-1), idx.reshape(-1)
    ].add(jnp.tile(weights.astype(jnp.int32), (state.depth,)))
    return state.replace(slots=slots)


def wcms_advance(state: WindowedCMS) -> WindowedCMS:
    """Rotate: move to the next slot and zero it (drop the oldest epoch)."""
    nxt = (state.epoch + 1) % state.n_slots
    slots = state.slots.at[nxt].set(0)
    return state.replace(slots=slots, epoch=nxt)


def _cms_estimate(table: jnp.ndarray, keys: jnp.ndarray,
                  log2_width: int) -> jnp.ndarray:
    """Count-min estimate of `keys` in one (depth, width) table."""
    idx = row_hashes(keys, table.shape[0], log2_width)
    return jnp.stack([table[d, idx[d]]
                      for d in range(table.shape[0])]).min(axis=0)


def wcms_query(state: WindowedCMS, keys: jnp.ndarray,
               last_k: int | None = None) -> jnp.ndarray:
    """Count estimate over the most recent `last_k` epochs (default: all
    live slots). Static `last_k` keeps the executable shape-stable."""
    k = state.n_slots if last_k is None else min(last_k, state.n_slots)
    # slot indices of the last k epochs, newest first
    offsets = jnp.arange(k, dtype=jnp.int32)
    live = (state.epoch - offsets) % state.n_slots          # (k,)
    table = state.slots[live].sum(axis=0)                   # (depth, width)
    return _cms_estimate(table, keys, state.log2_width)


def wcms_merge(a: WindowedCMS, b: WindowedCMS) -> WindowedCMS:
    """Slot-wise merge (epochs must be aligned across nodes — the cluster
    step advances all nodes' epochs together)."""
    return a.replace(slots=a.slots + b.slots)


def wcms_psum(state: WindowedCMS, axis_name: str) -> WindowedCMS:
    return state.replace(slots=jax.lax.psum(state.slots, axis_name))


# -- the history window's planes ----------------------------------------------

LATE_AGE = 255      # the age lane's mark of a late event (uint8)


@flax.struct.dataclass
class WindowPlanes:
    cms: WindowedCMS     # the ring; its epoch is the newest open window's slot
    hll: jnp.ndarray     # (open, 2^hll_p) int32: HLL registers per open window
    ent: jnp.ndarray     # (open, 2^ent_log2_width) int32: entropy histogram
    count: jnp.ndarray   # (open,) int32: events, exactly
    head: jnp.ndarray    # () int32: the newest open window's plane
    hll_p: int = flax.struct.field(pytree_node=False)
    ent_log2_width: int = flax.struct.field(pytree_node=False)

    @property
    def n_open(self) -> int:
        return self.count.shape[0]


def window_planes_init(n_open: int, n_slots: int, depth: int,
                       log2_width: int, hll_p: int,
                       ent_log2_width: int) -> WindowPlanes:
    """Planes for `n_open` windows beside a ring of `n_slots` >= n_open."""
    return WindowPlanes(
        cms=wcms_init(n_slots, depth, log2_width),
        hll=jnp.zeros((n_open, 1 << hll_p), jnp.int32),
        ent=jnp.zeros((n_open, 1 << ent_log2_width), jnp.int32),
        count=jnp.zeros((n_open,), jnp.int32),
        head=jnp.zeros((), jnp.int32),
        hll_p=hll_p, ent_log2_width=ent_log2_width)


def window_planes_step(p: WindowPlanes, hh: jnp.ndarray,
                       distinct: jnp.ndarray, dist: jnp.ndarray,
                       weights: jnp.ndarray, age: jnp.ndarray
                       ) -> tuple[WindowPlanes, jnp.ndarray]:
    """One batch into the planes of its windows: for each event the ring
    slot and the HLL, entropy and count planes of the window `age` behind
    the newest (`age` a scalar or a lane of uint8; LATE_AGE, or any age
    past the open planes, lands nowhere). `weights` are the staged uint32
    weights (0 on pad rows). Second output: the fence token."""
    n_open = p.n_open
    age = jnp.asarray(age).astype(jnp.int32)
    live = (age < n_open) & (weights > 0)
    wt = jnp.where(live, weights, 0).astype(jnp.int32)
    cms = wcms_update(p.cms, hh, wt, age)
    plane = jnp.broadcast_to((p.head - age) % n_open, wt.shape)
    reg, rank = hll_index_rank(distinct, p.hll_p)
    hll = p.hll.reshape(-1).at[plane * (1 << p.hll_p) + reg].max(
        jnp.where(live, rank, 0)).reshape(p.hll.shape)
    bucket = multiply_shift(dist, 0, p.ent_log2_width)
    ent = p.ent.reshape(-1).at[plane * (1 << p.ent_log2_width) + bucket].add(
        wt).reshape(p.ent.shape)
    # a few windows: a one-hot sum, where a scatter would collide n times
    onehot = plane[:, None] == jnp.arange(n_open, dtype=jnp.int32)
    count = p.count + jnp.where(onehot, wt[:, None], 0).sum(axis=0)
    out = p.replace(cms=cms, hll=hll, ent=ent, count=count)
    return out, out.count[:1] + 0


def window_capture(p: WindowPlanes, cand: jnp.ndarray, age: jnp.ndarray):
    """What a seal reads of the window `age` behind the newest: its
    count-min table, the candidates' estimates in it, its HLL registers,
    entropy histogram and count. Nothing is donated."""
    age = jnp.asarray(age, jnp.int32)
    table = p.cms.slots[(p.cms.epoch - age) % p.cms.n_slots]
    plane = (p.head - age) % p.n_open
    return (table, _cms_estimate(table, cand, p.cms.log2_width),
            p.hll[plane], p.ent[plane], p.count[plane])


def window_open(p: WindowPlanes) -> WindowPlanes:
    """A new newest window: the ring advanced (its next slot zeroed) and
    the next plane zeroed. The plane must be sealed already."""
    head = (p.head + 1) % p.n_open
    return p.replace(cms=wcms_advance(p.cms), head=head,
                     hll=p.hll.at[head].set(0), ent=p.ent.at[head].set(0),
                     count=p.count.at[head].set(0))


def window_close(p: WindowPlanes, cand: jnp.ndarray):
    """A processing-time seal in one program: `window_capture` of the open
    window, and the planes with the next one opened (`window_open`)."""
    return window_capture(p, cand, 0), window_open(p)
