"""Pallas TPU flash attention — the fused hot path for long windows.

The blockwise path (ring_attention.blockwise_attention) already avoids the
[T,T] score matrix, but XLA still round-trips each chunk's partial products
through HBM between scan steps. This kernel fuses the whole streaming
softmax into VMEM: scores, renormalization and the accumulator never leave
the core — the standard flash schedule mapped onto the MXU.

Schedule: grid (B·H, T/bq, T/bk) with the KV dimension 'arbitrary'
(sequential) so the (m, l, acc) scratch carries across KV steps; K/V
stream through VMEM one block per step, so VMEM use is O(block²) no matter
how long the window — T=64k compiles in the same footprint as T=2k. The
causal upper triangle costs nothing: masked-out KV blocks skip via pl.when.

Layout matches the rest of the attention plane: [B, T, H, D]. The wrapper
folds (B, H) into the grid, pads D to the 128-lane boundary and T to the
block size (zero-padding is exact: padded D contributes 0 to q·k, padded K
positions are masked, padded Q rows are sliced off).

Used as the `attn="flash"` backend of models/seqmodel.py; under sequence
parallelism it composes with the Ulysses all-to-all (head-sharded full
windows). The kernel compiles for the TPU only; `interpret=True` (the
Pallas interpreter, how the CPU tests exercise the same code) is always the
caller's explicit choice, never a quiet default.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG = -1e30  # finite "-inf": keeps exp() exact-zero without NaNs


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  block: int, t_real: int, causal: bool, scale: float,
                  n_kv: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    active = (kj <= qi) if causal else (kj >= 0)

    @pl.when(active)
    def _update():
        q = q_ref[0].astype(jnp.float32) * scale       # (bq, d)
        k = k_ref[0].astype(jnp.float32)               # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        pos_q = qi * block + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        pos_k = kj * block + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = pos_k < t_real                          # T padding
        if causal:
            keep = keep & (pos_q >= pos_k)
        s = jnp.where(keep, s, _NEG)
        m_prev = m_scr[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        m_scr[...] = m_new[:, None]
        l_scr[...] = (l_scr[:, 0] * corr + p.sum(axis=-1))[:, None]
        acc_scr[...] = acc_scr[...] * corr[:, None] + lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kj == n_kv - 1)
    def _finish():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[:, 0], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def flash_attention(q, k, v, causal: bool = True, block: int = 128,
                    scale: Optional[float] = None,
                    interpret: bool = False) -> jnp.ndarray:
    """Fused attention, layout [B, T, H, D] (matches full/blockwise/ring).
    Any T and D: both are padded to hardware boundaries internally.

    Differentiable: the forward pass is the fused Pallas kernel; the
    backward pass recomputes attention per query block under
    jax.checkpoint (see _recompute_ref) — the standard flash training
    trade: scores are recomputed at transpose time, never stored, so
    backward memory is O(chunk·T), not O(T²)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    return _flash(q, k, v, causal, block, scale, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal: bool, block: int, scale: float,
           interpret: bool) -> jnp.ndarray:
    b, t, h, d = q.shape
    t_pad = -t % block
    d_pad = -d % 128

    def fold(x):
        x = x.transpose(0, 2, 1, 3).reshape(b * h, t, d)
        return jnp.pad(x, ((0, 0), (0, t_pad), (0, d_pad)))

    qf, kf, vf = fold(q), fold(k), fold(v)
    tp, dp = t + t_pad, d + d_pad
    n_kv = tp // block
    kernel = functools.partial(_flash_kernel, block=block, t_real=t,
                               causal=causal, scale=scale, n_kv=n_kv)
    out = pl.pallas_call(
        kernel,
        grid=(b * h, tp // block, n_kv),
        in_specs=[
            pl.BlockSpec((1, block, dp), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block, dp), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, block, dp), lambda bh, i, j: (bh, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block, dp), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, tp, dp), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block, 1), jnp.float32),    # running max m
            pltpu.VMEM((block, 1), jnp.float32),    # running denom l
            pltpu.VMEM((block, dp), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out[:, :t, :d].reshape(b, h, t, d).transpose(0, 2, 1, 3)


def _recompute_ref(q, k, v, causal: bool, scale: float, chunk: int = 128):
    """Differentiable recompute target for the backward pass: attention
    computed independently per query block under jax.checkpoint, mapped
    with lax.map. Memory truly stays sub-quadratic in the backward:
    checkpoint keeps each block's [chunk, T] scores out of the residuals
    (recomputed at transpose time), and lax.map's transpose ACCUMULATES
    dk/dv across blocks in a carry — nothing is stacked per step, unlike
    vjp through a scan-with-carried-output (which would stack O(T²/chunk)
    residuals). Any T: q is padded to the chunk boundary; padded rows are
    sliced off so their cotangents are zero."""
    b, t, h, d = q.shape
    t_pad = -t % chunk
    nb = (t + t_pad) // chunk
    qt = jnp.pad(q, ((0, 0), (0, t_pad), (0, 0), (0, 0))
                 ).transpose(0, 2, 1, 3)                     # [B,H,Tp,D]
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    qblocks = qt.reshape(b, h, nb, chunk, d).transpose(2, 0, 1, 3, 4)
    pos_k = jnp.arange(t)

    @jax.checkpoint
    def body(args):
        qblk, i = args                                       # [B,H,chunk,D]
        s = jnp.einsum("bhqd,bhkd->bhqk", qblk, kt,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            pos_q = i * chunk + jnp.arange(chunk)
            s = jnp.where(pos_q[:, None] >= pos_k[None, :], s, _NEG)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, vt.astype(jnp.float32),
                          preferred_element_type=jnp.float32)

    out = lax.map(body, (qblocks, jnp.arange(nb)))           # [nb,B,H,chunk,D]
    out = out.transpose(1, 2, 0, 3, 4).reshape(b, h, t + t_pad, d)
    return out[:, :, :t].transpose(0, 2, 1, 3).astype(q.dtype)


def _flash_fwd(q, k, v, causal, block, scale, interpret):
    return _flash(q, k, v, causal, block, scale, interpret), (q, k, v)


def _flash_bwd(causal, block, scale, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(lambda q_, k_, v_: _recompute_ref(q_, k_, v_, causal,
                                                       scale), q, k, v)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)
