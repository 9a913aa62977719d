"""Pallas TPU kernels for the sketch hot path.

Design note: the wide count-min table (W=65536)
ingests fastest through XLA's native scatter-add — the sort/segment
machinery XLA emits for scatter is already near memory-bound. Where Pallas
wins is the *narrow* histogram planes (entropy sketch W≤4096, autoencoder
count-vector binning): there a one-hot matmul keeps all the work on the MXU
with zero scatter serialization — each grid step materializes a one-hot
tile in VMEM (never HBM) and accumulates weights @ onehot.

hist[w] = Σ_n weights[n] * [bucket(keys[n]) == w]

Kernel contract: fixed shapes, f32 accumulation (exact for batch counts
< 2^24), uint32 hashing on the VPU, fori_loop over batch chunks.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

N_CHUNK = 256    # batch rows per MXU matmul step
W_TILE = 1024    # histogram buckets per grid step, laid out as (8, 128)

# stable kernel names: a lowered step carries `kernel_name = "<name>"` on
# its tpu_custom_call, which is how a caller (chip_smoke.py, the compile
# tests, a trace reduction) tells the fused update from the reference path
# — that one runs the entropy histogram kernel on a TPU too
FUSED_KERNEL_NAME = "fused_sketch_planes"
HIST_KERNEL_NAME = "sketch_histogram"


def kernel_in_lowered(text: str, name: str) -> bool:
    """Whether `jitted.lower(...).as_text()` holds the named kernel."""
    return "tpu_custom_call" in text and f'kernel_name = "{name}"' in text


def _fmix32(h):
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def _u32_to_f32(v):
    """uint32 -> float32 without the direct convert (the TPU compiler has
    none in either direction): values below 2^31 go through int32; above,
    halve with a sticky low bit so the round-to-nearest-even result equals
    the direct convert's bit for bit."""
    lo = jax.lax.bitcast_convert_type(v, jnp.int32)
    half = jax.lax.bitcast_convert_type((v >> 1) | (v & 1), jnp.int32)
    return jnp.where(lo >= 0, lo.astype(jnp.float32),
                     half.astype(jnp.float32) * 2.0)


def _hist_kernel(keys_ref, w_ref, out_ref, *, log2_width: int, mult: int,
                 salt: int, n_chunks: int):
    tile = pl.program_id(0)

    def body(c, acc):
        keys = keys_ref[c, :]
        wk = w_ref[c, :]
        h = _fmix32(keys.astype(jnp.uint32) * jnp.uint32(mult)
                    + jnp.uint32(salt))
        idx = (h >> (32 - log2_width)).astype(jnp.int32)
        local = idx - tile * W_TILE  # bucket position inside this width tile
        onehot = (local[:, None] == jax.lax.broadcasted_iota(
            jnp.int32, (N_CHUNK, W_TILE), 1)).astype(jnp.float32)
        return acc + jnp.dot(wk[None, :], onehot,
                             preferred_element_type=jnp.float32)

    acc = jax.lax.fori_loop(
        0, n_chunks, body, jnp.zeros((1, W_TILE), jnp.float32))
    out_ref[0, :, :] = acc.reshape(8, 128)


@functools.partial(jax.jit, static_argnames=("log2_width", "mult", "salt"))
def pallas_histogram(keys: jnp.ndarray, weights: jnp.ndarray, *,
                     log2_width: int, mult: int = 0x9E3779B1,
                     salt: int = 0) -> jnp.ndarray:
    """(n,) uint32 keys + (n,) f32 weights → (2**log2_width,) f32 histogram.
    n must be a multiple of N_CHUNK; width a multiple of W_TILE (pad the
    sketch config, not the data)."""
    n = keys.shape[0]
    width = 1 << log2_width
    assert n % N_CHUNK == 0 and width % W_TILE == 0
    n_chunks = n // N_CHUNK
    keys2 = keys.reshape(n_chunks, N_CHUNK)
    w2 = weights.astype(jnp.float32).reshape(n_chunks, N_CHUNK)
    kernel = functools.partial(
        _hist_kernel, log2_width=log2_width, mult=mult, salt=salt,
        n_chunks=n_chunks)
    out = pl.pallas_call(
        kernel,
        grid=(width // W_TILE,),
        in_specs=[
            pl.BlockSpec((n_chunks, N_CHUNK), lambda t: (0, 0)),
            pl.BlockSpec((n_chunks, N_CHUNK), lambda t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 8, 128), lambda t: (t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((width // W_TILE, 8, 128), jnp.float32),
        name=HIST_KERNEL_NAME,
    )(keys2, w2)
    return out.reshape(width)


def xla_histogram(keys: jnp.ndarray, weights: jnp.ndarray, *,
                  log2_width: int, mult: int = 0x9E3779B1,
                  salt: int = 0) -> jnp.ndarray:
    """Scatter-add reference implementation (same hash)."""
    h = _fmix32(keys.astype(jnp.uint32) * jnp.uint32(mult) + jnp.uint32(salt))
    idx = (h >> (32 - log2_width)).astype(jnp.int32)
    return jnp.zeros(1 << log2_width, jnp.float32).at[idx].add(
        weights.astype(jnp.float32))


# ---------------------------------------------------------------------------
# Fused bundle_update kernel (ISSUE 10 tentpole; invertible planes ISSUE
# 15; DDSketch quantile plane ISSUE 16).
#
# SketchLib / NitroSketch observation: the order-of-magnitude win is ONE
# pass over the staged batch updating every sketch plane, instead of one
# dispatched op per sketch. This kernel folds the three histogram-shaped
# planes (depth count-min rows + the entropy buckets), the HLL
# register-max plane, and (when configured) the invertible sketch's
# count/key-sum/fingerprint lanes and the DDSketch latency-quantile row
# into a single pallas_call:
#
#   grid = (n_planes, Wmax/W_TILE),
#   n_planes = depth + 2 + 3*inv_rows + (1 if quantiles)
#   plane 0..depth-1   CMS row d:  h = fmix32(hh * mult_d + salt_d)
#   plane depth        entropy:    h = fmix32(dist * mult_0)
#   plane depth+1      HLL:        h = fmix32(distinct); value = rank,
#                                  combined by MAX instead of ADD
#   plane depth+2+3r+l invertible row r, lane l ∈ {count, keysum,
#                                  fpsum}: 32-bit integer accumulation
#                                  (wraps mod 2^32 — the invertible
#                                  algebra), bitcast to f32 bits for the
#                                  output
#   last plane         quantiles:  bucket = ceil(log_gamma(value)) (no
#                                  hashing — DDSketch's log-spaced bins),
#                                  one-hot histogram of the value lane;
#                                  zero-valued rows weigh 0 (they land in
#                                  the host-side zero bucket)
#
# Every plane is padded to the widest plane's tile count so the grid and
# index maps stay trivial; tiles past a narrow plane's real width can
# never match a bucket index and write zero blocks that the host-side
# wrapper slices off (bounded wasted VPU work, shape-generic kernel).
# Histogram accumulation is f32 — exact for per-batch bucket deltas
# < 2^24 (the staged batch is <= 2^17 rows), so casting the deltas back
# to the sketches' int32 state is bit-identical to the reference scatter
# path. The invertible lanes accumulate in 32-bit INTEGERS on the VPU
# (key*weight products overflow f32's 24-bit mantissa, and mod-2^32 wrap is
# the semantics, not an error): uint32 products, summed on their int32 view
# because the TPU compiler has no unsigned reduction — the same bits. So
# they are bit-identical by construction; the parity tier in
# tests/test_sketches.py holds every path to that contract. The compiler
# also has no uint32<->float32 convert in either direction: the weights
# lane goes through int32 and the value lane through _u32_to_f32.
# ---------------------------------------------------------------------------


def _fused_kernel(hh_ref, distinct_ref, dist_ref, w_ref, *rest,
                  depth: int, log2_width: int, ent_log2_width: int,
                  hll_p: int, inv_rows: int, inv_log2_buckets: int,
                  qt_buckets: int, qt_inv_log_gamma: float,
                  qt_offset: float, qt_min_value: float, n_chunks: int):
    # the quantile plane adds a 5th input ref (the value lane); pallas
    # passes output refs after input refs, so unpack positionally
    if qt_buckets:
        values_ref, out_ref = rest
    else:
        (out_ref,) = rest
    plane = pl.program_id(0)
    tile = pl.program_id(1)

    # per-plane hash parameters, selected by the traced plane id through
    # scalar where-chains (immediates — a pallas kernel cannot capture
    # host-built constant arrays); the multipliers mirror
    # ops.hashing._row_multiplier's seed table so the fused state merges
    # coherently with every other process
    from .hashing import _row_multiplier

    def sel(vals):
        out = jnp.uint32(vals[-1])
        for i in range(len(vals) - 2, -1, -1):
            out = jnp.where(plane == i, jnp.uint32(vals[i]), out)
        return out

    mult = sel([int(_row_multiplier(d)) for d in range(depth)]
               + [int(_row_multiplier(0)), 1])
    salt = sel([(d * 0x9E3779B9) & 0xFFFFFFFF for d in range(depth)]
               + [0, 0])
    shift = sel([32 - log2_width] * depth
                + [32 - ent_log2_width, 32 - hll_p])
    iota = jax.lax.broadcasted_iota(jnp.int32, (N_CHUNK, W_TILE), 1)

    def hist_body(c, acc):
        keys = jnp.where(plane < depth, hh_ref[c, :], dist_ref[c, :])
        wk = w_ref[c, :]
        h = _fmix32(keys.astype(jnp.uint32) * mult + salt)
        idx = (h >> shift).astype(jnp.int32)
        local = idx - tile * W_TILE
        onehot = (local[:, None] == iota).astype(jnp.float32)
        return acc + jnp.dot(wk[None, :], onehot,
                             preferred_element_type=jnp.float32)

    def hll_body(c, acc):
        keys = distinct_ref[c, :]
        wk = w_ref[c, :]
        h = _fmix32(keys.astype(jnp.uint32))
        idx = (h >> (32 - hll_p)).astype(jnp.int32)
        # rank = leading zeros of the remaining (32-p) bits, +1 — the
        # exact ops.hll.hll_update formula, masked rows contribute 0
        rest = (h << hll_p) | jnp.uint32((1 << hll_p) - 1)
        rank = jnp.clip(jax.lax.clz(rest.astype(jnp.int32)), 0, 32 - hll_p) + 1
        rank = jnp.where(wk > 0, rank, 0).astype(jnp.float32)
        local = idx - tile * W_TILE
        contrib = jnp.where(local[:, None] == iota, rank[:, None], 0.0)
        return jnp.maximum(acc, contrib.max(axis=0, keepdims=True))

    zero = jnp.zeros((1, W_TILE), jnp.float32)

    def run_hll():
        return jax.lax.fori_loop(0, n_chunks, hll_body, zero)

    def run_hist():
        return jax.lax.fori_loop(0, n_chunks, hist_body, zero)

    def base_dispatch():
        return jax.lax.cond(plane == depth + 1, run_hll, run_hist)

    if qt_buckets:
        # DDSketch row: same one-hot MXU histogram as the CMS/entropy
        # planes, but the bucket index is the log-gamma bin of the VALUE
        # lane (no hashing) — the exact ops.quantiles._bucket_index
        # expression, constants folded in as immediates so interpret-mode
        # parity with the reference scatter path is bit-identical.
        # Zero-valued rows weigh 0 here; the wrapper accounts them in the
        # sketch's zero bucket (dd_update's is_zero term).
        def qt_body(c, acc):
            vals = _u32_to_f32(values_ref[c, :])
            wk = w_ref[c, :]
            v = jnp.maximum(vals, qt_min_value)
            idx = jnp.ceil(jnp.log(v) * qt_inv_log_gamma - qt_offset)
            idx = jnp.clip(idx, 0, qt_buckets - 1).astype(jnp.int32)
            wpos = jnp.where(vals > 0, wk, 0.0)
            local = idx - tile * W_TILE
            onehot = (local[:, None] == iota).astype(jnp.float32)
            return acc + jnp.dot(wpos[None, :], onehot,
                                 preferred_element_type=jnp.float32)

        def run_qt():
            return jax.lax.fori_loop(0, n_chunks, qt_body, zero)

        qt_plane = depth + 2 + 3 * inv_rows

    if inv_rows:
        # invertible planes: bucket-hash parameters per ROW (3 planes
        # share a row), the lane kind (count/keysum/fpsum) selected by
        # plane id mod 3; all arithmetic 32-bit integer so the mod-2^32 wrap
        # the decode inverts happens natively, then the accumulator's bits
        # ride the f32 output via bitcast (memory moves only — no f32
        # arithmetic ever touches them)
        from .invertible import FP_SALT, INV_ROW_OFFSET
        inv_base = depth + 2

        def sel_inv(vals):
            out = jnp.uint32(vals[-1])
            for i in range(len(vals) - 2, -1, -1):
                out = jnp.where(plane == inv_base + i, jnp.uint32(vals[i]),
                                out)
            return out

        imult = sel_inv([int(_row_multiplier(INV_ROW_OFFSET + p // 3))
                         for p in range(3 * inv_rows)])
        isalt = sel_inv([((INV_ROW_OFFSET + p // 3) * 0x9E3779B9)
                         & 0xFFFFFFFF for p in range(3 * inv_rows)])
        lane = (plane - inv_base) % 3

        def inv_body(c, acc):
            keys = hh_ref[c, :].astype(jnp.uint32)
            wu = w_ref[c, :].astype(jnp.int32).astype(jnp.uint32)
            h = _fmix32(keys * imult + isalt)
            idx = (h >> (32 - inv_log2_buckets)).astype(jnp.int32)
            local = idx - tile * W_TILE
            fpv = _fmix32(keys ^ jnp.uint32(FP_SALT))
            val = jnp.where(lane == 0, wu,
                            jnp.where(lane == 1, keys * wu, fpv * wu))
            # the column sum runs on the int32 view: the TPU compiler has
            # no unsigned reduction, and two's-complement adds wrap mod
            # 2^32 exactly as the uint32 ones do
            val = jax.lax.bitcast_convert_type(val, jnp.int32)
            contrib = jnp.where(local[:, None] == iota, val[:, None], 0)
            return acc + contrib.sum(axis=0, keepdims=True)

        def run_inv():
            acc_i = jax.lax.fori_loop(
                0, n_chunks, inv_body, jnp.zeros((1, W_TILE), jnp.int32))
            return jax.lax.bitcast_convert_type(acc_i, jnp.float32)

        def inv_dispatch():
            return jax.lax.cond(plane >= inv_base, run_inv, base_dispatch)

        # the quantile plane sits LAST (id >= inv_base), so it must win
        # the dispatch before the `plane >= inv_base` invertible test
        acc = (jax.lax.cond(plane == qt_plane, run_qt, inv_dispatch)
               if qt_buckets else inv_dispatch())
    elif qt_buckets:
        acc = jax.lax.cond(plane == qt_plane, run_qt, base_dispatch)
    else:
        acc = base_dispatch()
    out_ref[0, 0, :, :] = acc.reshape(8, 128)


@functools.partial(jax.jit, static_argnames=(
    "depth", "log2_width", "ent_log2_width", "hll_p", "inv_rows",
    "inv_log2_buckets", "qt_buckets", "qt_alpha", "qt_min_value",
    "interpret"))
def fused_sketch_planes(hh_keys: jnp.ndarray, distinct_keys: jnp.ndarray,
                        dist_keys: jnp.ndarray, weights: jnp.ndarray,
                        values: jnp.ndarray | None = None, *,
                        depth: int, log2_width: int, ent_log2_width: int,
                        hll_p: int, inv_rows: int = 0,
                        inv_log2_buckets: int = 0, qt_buckets: int = 0,
                        qt_alpha: float = 0.01, qt_min_value: float = 1.0,
                        interpret: bool = False
                        ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                                   jnp.ndarray | None, jnp.ndarray | None]:
    """One fused pass over the staged batch → per-plane state deltas:
    (cms_delta (depth, W) f32, ent_delta (2**ent_log2_width,) f32,
    hll_batch_ranks (2**hll_p,) f32, inv_delta (inv_rows, 3,
    2**inv_log2_buckets) uint32 or None, qt_delta (qt_buckets,) f32 or
    None). The invertible deltas come back already bitcast to uint32
    with lanes ordered (count, keysum, fpsum) per row. The quantile
    delta is the DDSketch bucket histogram of the `values` lane (uint32,
    required when qt_buckets > 0); zero values carry no positive-bucket
    weight. n must be a multiple of N_CHUNK and the WIDEST plane a
    multiple of W_TILE (pad the sketch config, not the data).
    `interpret=True` runs the kernel in the Pallas interpreter — how the
    parity tier exercises the kernel math on CPU CI."""
    n = hh_keys.shape[0]
    wmax = max(1 << log2_width, 1 << ent_log2_width, 1 << hll_p,
               (1 << inv_log2_buckets) if inv_rows else 0,
               qt_buckets)
    assert n % N_CHUNK == 0 and wmax % W_TILE == 0
    if qt_buckets:
        assert values is not None, "qt plane needs the value lane"
    n_chunks = n // N_CHUNK
    n_planes = depth + 2 + 3 * inv_rows + (1 if qt_buckets else 0)
    tiles = wmax // W_TILE
    shape2 = (n_chunks, N_CHUNK)
    w2 = weights.astype(jnp.float32).reshape(shape2)
    # static DDSketch constants, folded into the trace exactly as the
    # reference ops.quantiles._bucket_index computes them on the host
    gamma = (1.0 + qt_alpha) / (1.0 - qt_alpha)
    qt_ilg = 1.0 / math.log(gamma) if qt_buckets else 0.0
    qt_off = math.log(qt_min_value) * qt_ilg if qt_buckets else 0.0
    kernel = functools.partial(
        _fused_kernel, depth=depth, log2_width=log2_width,
        ent_log2_width=ent_log2_width, hll_p=hll_p, inv_rows=inv_rows,
        inv_log2_buckets=inv_log2_buckets, qt_buckets=qt_buckets,
        qt_inv_log_gamma=qt_ilg, qt_offset=qt_off,
        qt_min_value=qt_min_value, n_chunks=n_chunks)
    batch_spec = pl.BlockSpec(shape2, lambda p, t: (0, 0))
    operands = [hh_keys.reshape(shape2), distinct_keys.reshape(shape2),
                dist_keys.reshape(shape2), w2]
    if qt_buckets:
        operands.append(values.reshape(shape2))
    out = pl.pallas_call(
        kernel,
        grid=(n_planes, tiles),
        in_specs=[batch_spec] * len(operands),
        out_specs=pl.BlockSpec((1, 1, 8, 128), lambda p, t: (p, t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_planes, tiles, 8, 128),
                                       jnp.float32),
        interpret=interpret,
        name=FUSED_KERNEL_NAME,
    )(*operands)
    out = out.reshape(n_planes, wmax)
    inv_delta = None
    if inv_rows:
        inv_bits = out[depth + 2:depth + 2 + 3 * inv_rows,
                       :1 << inv_log2_buckets]
        inv_delta = jax.lax.bitcast_convert_type(
            inv_bits, jnp.uint32).reshape(inv_rows, 3,
                                          1 << inv_log2_buckets)
    qt_delta = (out[depth + 2 + 3 * inv_rows, :qt_buckets]
                if qt_buckets else None)
    return (out[:depth, :1 << log2_width],
            out[depth, :1 << ent_log2_width],
            out[depth + 1, :1 << hll_p],
            inv_delta,
            qt_delta)
