"""SketchBundle: the per-node analytics state updated once per event batch.

This is the device-side hot loop of the framework — the TPU analogue of the
reference's per-event Go hot loop (perf.Reader.Read → enrich → format,
pkg/gadgets/trace/exec/tracer/tracer.go:134-188). One jitted step absorbs a
fixed-shape batch into all sketches; with jax.block_until_ready only at
harvest points, ingest stays pipelined.

Key streams per batch (all uint32, padded to fixed length with mask):
  hh_keys       heavy-hitter keys (count-min + top-k), e.g. hash(comm)
  distinct_keys HLL distinct stream, e.g. hash(saddr,daddr,dport)
  dist_keys     distribution stream (entropy + anomaly vector), e.g. syscall
"""

from __future__ import annotations

import os

import flax.struct
import jax
import jax.numpy as jnp

import numpy as np

from .countmin import CountMin, cms_init, cms_merge, cms_update
from .entropy import (EntropySketch, entropy_estimate, entropy_init,
                      entropy_merge, entropy_update)
from .hll import HLL, hll_estimate, hll_init, hll_merge, hll_update
from .invertible import InvSketch, inv_init, inv_merge, inv_update
from .quantiles import DDSketch, dd_init, dd_merge, dd_update
from .topk import TopK, topk_init, topk_merge, topk_update


@flax.struct.dataclass
class SketchBundle:
    cms: CountMin
    hll: HLL
    entropy: EntropySketch
    topk: TopK
    events: jnp.ndarray  # () float32 — total events absorbed (masked count)
    drops: jnp.ndarray   # () float32 — upstream loss accounting carried along
    # invertible heavy-key plane (ISSUE 15): None for configs without it,
    # so every pre-existing treedef (and every level-0 window digest of a
    # plane-off config) is unchanged; when present it rides every merge
    # path for free — pairwise adds, cluster psum, lane stacking
    inv: InvSketch | None = None
    # latency-quantile plane (ISSUE 16): the DDSketch row fed by the
    # per-event VALUE lane (latency ns / byte size); same None-default
    # contract as `inv` — plane-off treedefs, digests and checkpoints are
    # byte-identical to pre-plane builds, plane-on merges ride dd_merge /
    # dd_psum on every path
    quantiles: DDSketch | None = None


def bundle_init(
    *,
    depth: int = 4,
    log2_width: int = 16,
    hll_p: int = 14,
    entropy_log2_width: int = 12,
    k: int = 128,
    inv_rows: int = 0,
    inv_log2_buckets: int = 12,
    quantiles: bool = False,
    quantile_alpha: float = 0.01,
    quantile_buckets: int = 2048,
    quantile_min_value: float = 1.0,
) -> SketchBundle:
    # quantile_min_value defaults to 1.0 because the value lane is an
    # integer domain (nanoseconds / bytes): bucket 0 starts at 1 unit and
    # exact zeros go to the dedicated zero bucket
    return SketchBundle(
        cms=cms_init(depth, log2_width),
        hll=hll_init(hll_p),
        entropy=entropy_init(entropy_log2_width),
        topk=topk_init(k),
        events=jnp.zeros((), jnp.float32),
        drops=jnp.zeros((), jnp.float32),
        inv=(inv_init(inv_rows, inv_log2_buckets) if inv_rows else None),
        quantiles=(dd_init(alpha=quantile_alpha, n_buckets=quantile_buckets,
                           min_value=quantile_min_value)
                   if quantiles else None),
    )


def _values_or_zero(values, like: jnp.ndarray) -> jnp.ndarray:
    """Sources without a value lane feed zeros — every event lands in
    the DDSketch zero bucket, keeping totals honest."""
    return values if values is not None else jnp.zeros(like.shape,
                                                       jnp.uint32)


def bundle_update(
    bundle: SketchBundle,
    hh_keys: jnp.ndarray,
    distinct_keys: jnp.ndarray,
    dist_keys: jnp.ndarray,
    mask: jnp.ndarray,
    drops: jnp.ndarray | None = None,
    values: jnp.ndarray | None = None,
) -> SketchBundle:
    w = mask.astype(jnp.int32)
    cms = cms_update(bundle.cms, hh_keys, w)
    return bundle.replace(
        cms=cms,
        hll=hll_update(bundle.hll, distinct_keys, mask),
        entropy=entropy_update(bundle.entropy, dist_keys, w.astype(jnp.float32)),
        topk=topk_update(bundle.topk, cms, hh_keys, mask),
        events=bundle.events + mask.sum(dtype=jnp.float32),
        drops=bundle.drops + (drops if drops is not None else 0.0),
        inv=(inv_update(bundle.inv, hh_keys, w)
             if bundle.inv is not None else None),
        quantiles=(dd_update(bundle.quantiles,
                             _values_or_zero(values, hh_keys), w)
                   if bundle.quantiles is not None else None),
    )


def bundle_merge(a: SketchBundle, b: SketchBundle) -> SketchBundle:
    cms = cms_merge(a.cms, b.cms)
    return SketchBundle(
        cms=cms,
        hll=hll_merge(a.hll, b.hll),
        entropy=entropy_merge(a.entropy, b.entropy),
        topk=topk_merge(a.topk, b.topk, cms),
        events=a.events + b.events,
        drops=a.drops + b.drops,
        inv=(inv_merge(a.inv, b.inv)
             if a.inv is not None and b.inv is not None else None),
        quantiles=(dd_merge(a.quantiles, b.quantiles)
                   if a.quantiles is not None and b.quantiles is not None
                   else None),
    )


bundle_update_jit = jax.jit(bundle_update, donate_argnums=0)


# -- fused single-pass update (ISSUE 10 tentpole) ---------------------------
# One algorithm, two implementations whose costs scale differently with
# the geometry: the fused Pallas pass over the staged batch
# (ops/pallas_kernels.fused_sketch_planes) does one-hot work on every
# plane padded to the widest, the scatter composition bundle_update above
# touches one counter per plane and event. update_arm() picks between them
# at TRACE time from what it can see (backend, shapes); bundle_update also
# stays the reference implementation the parity tier holds the kernel to.
# IG_FUSED_DISABLE=1 means "never the kernel"; like the rest of the choice
# it is read at trace time, so it takes effect for any shape not yet
# compiled and already-cached traces keep their arm until retrace.

# Compare-selects the kernel does in the time the scatter composition
# touches one counter, read on a TPU v5e (PR 26; fused_expected_to_win has
# the table): the lowest of its readings, so the kernel is taken only
# where every reading says it wins.
FUSED_ONEHOTS_PER_COUNTER = 4700


def _fused_planes(bundle: SketchBundle) -> tuple[int, int]:
    """(planes, widest plane) as the fused kernel lays them out: one plane
    per count-min row, entropy, HLL, three per invertible row, one for the
    quantile row; every plane padded to the widest."""
    inv, qt = bundle.inv, bundle.quantiles
    wmax = max(bundle.cms.width, bundle.entropy.counts.shape[0],
               bundle.hll.registers.shape[0],
               inv.buckets if inv is not None else 0,
               qt.counts.shape[0] if qt is not None else 0)
    n_planes = (bundle.cms.depth + 2 + (3 * inv.rows if inv is not None else 0)
                + (1 if qt is not None else 0))
    return n_planes, wmax


def fused_supported(bundle: SketchBundle, n: int) -> bool:
    """Shape gate for the fused kernel: batch rows must tile into MXU
    chunks and the widest plane into lane tiles (pad the config, not the
    data); odd shapes take the reference path automatically. The
    invertible plane (when present) counts toward the widest plane like
    every other lane, as does the quantile row."""
    from .pallas_kernels import N_CHUNK, W_TILE
    return n % N_CHUNK == 0 and _fused_planes(bundle)[1] % W_TILE == 0


def fused_expected_to_win(bundle: SketchBundle, n: int) -> bool:
    """Whether the kernel is expected to be the faster arm for a batch of
    n rows. Its work is planes x widest plane x n one-hot compare-selects
    (every plane padded to the widest), the scatter composition's n x
    planes counters touched; FUSED_ONEHOTS_PER_COUNTER is the exchange
    rate between the two. The table it comes from (TPU v5e, ms a step
    without the top-k refresh both arms share, fused / scatter, at batch
    65,536 and 8,192; base = 6 planes, +inv 15, +qt 7):

      widest   base 65,536   +inv 65,536   +qt 65,536    base 8,192
      2^16     36.8 / 2.77   91.2 / 6.81   43.0 / 3.34   4.73 / 0.45
      2^14      9.30 / 2.78  22.9 / 6.80   10.9 / 3.34   1.27 / 0.44
      2^13      4.73 / 2.76  11.5 / 6.78    5.50 / 3.43  0.68 / 0.50
      2^12      2.42 / 2.78   5.86 / 6.80   2.81 / 3.40  0.51 / 0.49
      2^10      0.68 / 3.11   -             -            0.47 / 0.48

    The kernel's time is 1.43 ps a compare-select in every row and the
    scatter's 7.0-9.2 ns a counter, so the arms cross at a widest plane of
    4,700-6,300 whatever the batch and the plane set (the work ratio is the
    widest plane). Whole steps, top-k refresh included: 6.24 / 6.60 ms at
    2^12 and 40.6 / 6.63 ms at 2^16 (batch 65,536); 0.88 / 0.94 at 2^12,
    1.17 / 0.92 at 2^13 (batch 8,192)."""
    n_planes, wmax = _fused_planes(bundle)
    onehots = n_planes * wmax * n
    counters = n_planes * n
    return onehots <= FUSED_ONEHOTS_PER_COUNTER * counters


def update_arm(bundle: SketchBundle, n: int) -> str:
    """The arm bundle_update_fused takes for a batch of n rows, decided
    where it traces: "fused" (the kernel: on a TPU, shapes aligned, expected
    to win, not disabled) or "scatter" (the reference composition)."""
    if (os.environ.get("IG_FUSED_DISABLE", "") != "1"
            and jax.default_backend() == "tpu"
            and fused_supported(bundle, n)
            and fused_expected_to_win(bundle, n)):
        return "fused"
    return "scatter"


def _bundle_update_pallas(
    bundle: SketchBundle,
    hh_keys: jnp.ndarray,
    distinct_keys: jnp.ndarray,
    dist_keys: jnp.ndarray,
    mask: jnp.ndarray,
    drops: jnp.ndarray | None = None,
    values: jnp.ndarray | None = None,
    *,
    interpret: bool = False,
) -> SketchBundle:
    """Assemble the next bundle from the fused kernel's per-plane deltas.
    Every expression mirrors the reference ops bit-for-bit: f32 deltas are
    exact integers for batches < 2^24 rows, int32 casts are exact, the
    top-k refresh is the SAME topk_update against the already-updated CMS.
    Exposed (with interpret=True) to the parity tier; production entry is
    bundle_update_fused below."""
    from .pallas_kernels import fused_sketch_planes
    w_i32 = mask.astype(jnp.int32)
    inv_rows = bundle.inv.rows if bundle.inv is not None else 0
    inv_lb = bundle.inv.log2_buckets if bundle.inv is not None else 0
    qt = bundle.quantiles
    vals = (_values_or_zero(values, hh_keys) if qt is not None else None)
    cms_d, ent_d, ranks, inv_d, qt_d = fused_sketch_planes(
        hh_keys, distinct_keys, dist_keys, w_i32, vals,
        depth=bundle.cms.depth, log2_width=bundle.cms.log2_width,
        ent_log2_width=bundle.entropy.log2_width, hll_p=bundle.hll.p,
        inv_rows=inv_rows, inv_log2_buckets=inv_lb,
        qt_buckets=(qt.counts.shape[0] if qt is not None else 0),
        qt_alpha=(qt.alpha if qt is not None else 0.01),
        qt_min_value=(qt.min_value if qt is not None else 1.0),
        interpret=interpret)
    cms = bundle.cms.replace(
        table=bundle.cms.table + cms_d.astype(bundle.cms.table.dtype),
        total=bundle.cms.total + w_i32.sum().astype(jnp.float32))
    inv = None
    if bundle.inv is not None:
        # the kernel already accumulated in uint32 (wraps mod 2^32 — the
        # invertible algebra itself), so the adds below are the same
        # integer adds the reference scatter path performs, bit for bit;
        # the count delta fits int32 (per-batch weight sums << 2^31)
        inv = bundle.inv.replace(
            count=bundle.inv.count + inv_d[:, 0].astype(jnp.int32),
            keysum=bundle.inv.keysum + inv_d[:, 1],
            fpsum=bundle.inv.fpsum + inv_d[:, 2])
    if qt is not None:
        # zero/total accounting mirrors dd_update exactly; the kernel's
        # per-batch f32 bucket histogram is an exact integer (< 2^24), so
        # the int32 cast matches the reference scatter-add bit for bit
        is_zero = jnp.where(vals <= 0, w_i32, 0)
        qt = qt.replace(
            counts=qt.counts + qt_d.astype(jnp.int32),
            zeros=qt.zeros + is_zero.sum(),
            total=qt.total + w_i32.sum())
    return bundle.replace(
        cms=cms,
        hll=bundle.hll.replace(registers=jnp.maximum(
            bundle.hll.registers, ranks.astype(jnp.int32))),
        entropy=bundle.entropy.replace(
            counts=bundle.entropy.counts + ent_d),
        topk=topk_update(bundle.topk, cms, hh_keys, mask),
        events=bundle.events + mask.sum(dtype=jnp.float32),
        drops=bundle.drops + (drops if drops is not None else 0.0),
        inv=inv,
        quantiles=qt,
    )


def bundle_update_fused(
    bundle: SketchBundle,
    hh_keys: jnp.ndarray,
    distinct_keys: jnp.ndarray,
    dist_keys: jnp.ndarray,
    mask: jnp.ndarray,
    drops: jnp.ndarray | None = None,
    values: jnp.ndarray | None = None,
) -> SketchBundle:
    """Drop-in bundle_update replacement: the arm update_arm() names for
    these shapes. Both arms produce bit-identical state
    (tests/test_sketches.py parity tier)."""
    if update_arm(bundle, hh_keys.shape[0]) == "fused":
        return _bundle_update_pallas(bundle, hh_keys, distinct_keys,
                                     dist_keys, mask, drops, values)
    return bundle_update(bundle, hh_keys, distinct_keys, dist_keys, mask,
                         drops, values)


def bundle_ingest_step(
    bundle: SketchBundle,
    hh_keys: jnp.ndarray,
    distinct_keys: jnp.ndarray,
    dist_keys: jnp.ndarray,
    weights: jnp.ndarray,
    drops: jnp.ndarray | None = None,
    values: jnp.ndarray | None = None,
) -> tuple[SketchBundle, jnp.ndarray]:
    """The staged-ingest step the tpusketch operator dispatches — two
    contracts live here, once:

    - `weights` is the FoldedBatch weights lane as integer per-event
      weights: pad slots weigh 0, and a capture shim that pre-aggregates
      runs of equal keys may weigh a slot > 1 — CMS/entropy/events absorb
      the magnitude, HLL/top-k consult only nonzero-ness. A boolean mask
      is the weights∈{0,1} special case.
    - the second return is the FENCE TOKEN: a fresh scalar output the
      H2DStager blocks on before recycling the staged host block. The
      bundle itself can never be the fence — the NEXT step donates
      (deletes) it, and blocking on a donated buffer is an error; the
      token buffer is never donated downstream.
    """
    out = bundle_update_fused(bundle, hh_keys, distinct_keys, dist_keys,
                              weights.astype(jnp.int32), drops, values)
    return out, out.events + 0.0


bundle_ingest_jit = jax.jit(bundle_ingest_step, donate_argnums=0)


# -- multi-chip sharded ingest (ISSUE 14 tentpole) --------------------------
# One fused SketchBundle replica per chip, stacked on a leading lane axis
# and sharded over the (node) mesh: the ingest step is shard_map'd
# bundle_update_fused with NO cross-chip traffic (each lane absorbs its
# own staged batch), and the harvest is the only collective — psum for
# the additive planes (CMS table/total, entropy counts, events, drops),
# pmax for HLL registers, candidate union + re-rank against the merged
# CMS for top-k. The merge algebra is the PR-6/7 one (cluster_merge),
# so the harvested bundle is bit-identical to the single-chip fold of
# the same event stream: integer adds commute, register max commutes,
# and the top-k re-rank is a deterministic function of (candidate set,
# merged CMS) — tests/test_sharded_ingest.py pins every leaf across
# 1/2/4/8 lanes, ragged tails, and mid-run harvests.
#
# parallel.* imports stay inside the makers: parallel.cluster imports
# THIS module, so a module-level import here would be a cycle (and the
# makers run once per operator instance, not per batch).


def bundle_stack_sharded(bundle: SketchBundle, mesh) -> SketchBundle:
    """Stack `bundle` into lane 0 of a (chips, ...) lane-stacked bundle
    (lanes 1..n-1 start empty) sharded over the mesh's node axis. Seeding
    lane 0 with live state keeps checkpoint-resume semantics: the psum
    harvest absorbs the resumed counts exactly once."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel.mesh import NODE_AXIS
    n = mesh.shape[NODE_AXIS]

    def stack(x):
        z = jnp.zeros((n,) + x.shape, x.dtype).at[0].set(x)
        return jax.device_put(z, NamedSharding(mesh, P(NODE_AXIS)))

    return jax.tree.map(stack, bundle)


def _lane_specs(like: SketchBundle, spec):
    return jax.tree.map(lambda _: spec, like)


def make_bundle_ingest_sharded(mesh, like: SketchBundle):
    """Jitted sharded ingest step: (stacked_bundle, hh, distinct, dist,
    weights, drops) -> (stacked_bundle, fence_token).

    Batch arrays are (chips, batch) sharded over the node axis; `drops`
    is a (chips,) float32 lane vector. Each shard runs the SAME
    bundle_update_fused step the single-chip path runs (weights-lane
    semantics and the fused-vs-reference dispatch are inherited from
    bundle_ingest_step / bundle_update_fused — one contract, every
    path). The token is the per-lane events vector: fresh output each
    step, never donated downstream, so every lane's H2DStager can fence
    block recycling on it (the PR-7 fence contract, per lane)."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    from ..parallel.mesh import NODE_AXIS

    specs = _lane_specs(like, P(NODE_AXIS))
    lane = P(NODE_AXIS)

    if like.quantiles is not None:
        # quantile-plane configs stage one more lane: (chips, batch)
        # uint32 values, sharded like the key lanes
        def body_qt(bund, hh, distinct, dist, weights, drops, values):
            local = jax.tree.map(lambda x: x[0], bund)
            out = bundle_update_fused(local, hh[0], distinct[0], dist[0],
                                      weights[0].astype(jnp.int32),
                                      drops[0], values[0])
            return jax.tree.map(lambda x: x[None], out), out.events[None]

        return jax.jit(
            shard_map(body_qt, mesh=mesh,
                      in_specs=(specs, lane, lane, lane, lane, lane, lane),
                      out_specs=(specs, lane), check_vma=False),
            donate_argnums=0)

    def body(bund, hh, distinct, dist, weights, drops):
        local = jax.tree.map(lambda x: x[0], bund)
        out = bundle_update_fused(local, hh[0], distinct[0], dist[0],
                                  weights[0].astype(jnp.int32), drops[0])
        return jax.tree.map(lambda x: x[None], out), out.events[None]

    return jax.jit(
        shard_map(body, mesh=mesh,
                  in_specs=(specs, lane, lane, lane, lane, lane),
                  out_specs=(specs, lane), check_vma=False),
        donate_argnums=0)


def make_bundle_harvest_sharded(mesh, like: SketchBundle):
    """Jitted collective harvest: lane-stacked sharded bundle -> ONE
    replicated merged SketchBundle. The body IS parallel.cluster's
    cluster_merge (psum CMS/entropy/events/drops, pmax HLL, all_gather +
    re-rank top-k) — the same algebra the fleet merge uses, so device
    counts cannot fork the math. Never donates: harvest reads the live
    lane bundles while ingest keeps updating them."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    from ..parallel.cluster import cluster_merge
    from ..parallel.mesh import NODE_AXIS

    specs = _lane_specs(like, P(NODE_AXIS))
    out_specs = _lane_specs(like, P())
    return jax.jit(
        shard_map(cluster_merge, mesh=mesh, in_specs=(specs,),
                  out_specs=out_specs, check_vma=False),
        donate_argnums=())


def bundle_digest(b: SketchBundle) -> jnp.ndarray:
    """Harvest digest as ONE u32 array so a harvest tick costs a single
    blocking D2H read instead of six. Layout: [bitcast_f32(events, drops, distinct, entropy_bits,
    candidate_overflow), topk keys..k, topk counts..k (cast, exact)].
    Decode with decode_digest()."""
    meta = jnp.stack([b.events, b.drops,
                      hll_estimate(b.hll).astype(jnp.float32),
                      entropy_estimate(b.entropy).astype(jnp.float32),
                      b.topk.overflow.astype(jnp.float32)])
    return jnp.concatenate([
        jax.lax.bitcast_convert_type(meta, jnp.uint32),
        b.topk.keys,
        b.topk.counts.astype(jnp.uint32),
    ])


# DONATION CONTRACT (ISSUE 10 satellite): bundle_digest must NEVER donate
# its input. Harvest dispatches this on the LIVE bundle while the
# double-buffered ingest path keeps updating from the same reference —
# bundle_update_fused_jit (donate_argnums=0) deletes the buffers it is
# handed, so a donating digest would leave the next update reading
# deleted arrays. donate_argnums=() pins the contract explicitly; the
# regression test lives next to the PR-1 checkpoint-race test
# (tests/test_telemetry.py::test_harvest_digest_survives_update_pressure).
bundle_digest_jit = jax.jit(bundle_digest, donate_argnums=())


def decode_digest(digest) -> tuple[float, float, float, float, bool,
                                   np.ndarray, np.ndarray]:
    """Host-side decode of bundle_digest's packed array →
    (events, drops, distinct, entropy_bits, candidate_overflow,
    topk_keys_u32, topk_counts)."""
    d = np.asarray(digest)
    meta = d[:5].view(np.float32)
    k = (d.size - 5) // 2
    return (float(meta[0]), float(meta[1]), float(meta[2]), float(meta[3]),
            bool(meta[4] > 0), d[5:5 + k], d[5 + k:].astype(np.int64))
