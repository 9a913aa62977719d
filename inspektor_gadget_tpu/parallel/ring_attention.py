"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference has no long-context machinery (SURVEY.md §5: its unbounded-
stream analogue is snapshot/TTL merging). In the TPU build, long *event
sequences* are first-class model inputs: the sequence anomaly scorer
(models/seqmodel.py) attends over windows of 10^4-10^6 syscall tokens per
container, far beyond one chip's activation memory. This module provides
the three standard TPU-native attention layouts for that regime:

- ``blockwise_attention``: single-chip flash-style streaming softmax over
  KV chunks via ``lax.scan`` — O(T·chunk) memory instead of O(T^2).
- ``ring_attention``: sequence sharded over a mesh axis; KV blocks rotate
  hop-by-hop with ``lax.ppermute`` while each device accumulates its
  queries' partial softmax (running max / denominator / numerator). The
  per-hop message is one KV block, so the collective rides ICI neighbor
  links and overlaps with the block matmul.
- ``ulysses_attention``: ``lax.all_to_all`` re-shards sequence ↔ heads so
  each device runs *full* attention for a head subset — cheaper than the
  ring when heads ≥ devices and T fits after the head split.

All accumulate in float32 regardless of input dtype (bf16 inputs stay bf16
through the matmuls feeding the MXU; the softmax state is f32).

Inner functions are written for use under ``jax.shard_map`` with a mesh
axis carrying the sequence dimension; `make_*` helpers wrap them.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_NEG = jnp.float32(-1e30)  # finite "-inf": keeps exp() exact-zero without NaNs


def _block_update(q, k, v, o, m, l, pos_q, pos_k, causal: bool, scale):
    """One streaming-softmax accumulation step.

    q: [B,H,Tq,D]; k,v: [B,H,Tk,D]; o: [B,H,Tq,D] f32; m,l: [B,H,Tq] f32.
    Returns updated (o, m, l). Fully-masked rows are harmless: scores are
    -1e30, so the incoming block contributes exp(-1e30 - m_new) = 0.
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        mask = pos_q[:, None] >= pos_k[None, :]
        s = jnp.where(mask, s, _NEG)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + p.sum(axis=-1)
    o_new = o * corr[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(jnp.float32),
        preferred_element_type=jnp.float32)
    return o_new, m_new, l_new


def _finish(o, l, dtype):
    return (o / jnp.maximum(l, 1e-30)[..., None]).astype(dtype)


def full_attention(q, k, v, causal: bool = True,
                   scale: Optional[float] = None) -> jnp.ndarray:
    """Materialized-scores reference. Layout [B, T, H, D]."""
    scale = scale or q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        t = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32),
                      preferred_element_type=jnp.float32).astype(q.dtype)


def blockwise_attention(q, k, v, causal: bool = True, chunk: int = 128,
                        scale: Optional[float] = None) -> jnp.ndarray:
    """Single-device flash-style attention: lax.scan over KV chunks.

    Layout [B, T, H, D]; T must be divisible by `chunk`. Memory is
    O(B·H·T·D + B·H·T·chunk) — the full [T,T] score matrix never exists.
    """
    b, t, h, d = q.shape
    scale = scale or d ** -0.5
    qt = q.transpose(0, 2, 1, 3)  # [B,H,T,D]
    kt = k.transpose(0, 2, 1, 3).reshape(b, h, t // chunk, chunk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b, h, t // chunk, chunk, d)
    pos_q = jnp.arange(t)
    o0 = jnp.zeros((b, h, t, d), jnp.float32)
    m0 = jnp.full((b, h, t), _NEG)
    l0 = jnp.zeros((b, h, t), jnp.float32)

    def step(carry, inp):
        o, m, l = carry
        (kc, vc, ci) = inp
        pos_k = ci * chunk + jnp.arange(chunk)
        o, m, l = _block_update(qt, kc, vc, o, m, l, pos_q, pos_k,
                                causal, scale)
        return (o, m, l), None

    (o, _, l), _ = lax.scan(
        step, (o0, m0, l0),
        (kt.transpose(2, 0, 1, 3, 4), vt.transpose(2, 0, 1, 3, 4),
         jnp.arange(t // chunk)))
    return _finish(o, l, q.dtype).transpose(0, 2, 1, 3)


def ring_attention(q, k, v, axis_name: str, causal: bool = True,
                   scale: Optional[float] = None) -> jnp.ndarray:
    """Ring attention over a sharded sequence (call under shard_map).

    q/k/v hold this device's sequence shard, layout [B, T_local, H, D];
    global position of local row i is ``rank * T_local + i``. KV blocks
    rotate rank → rank+1 each hop (N hops total); queries never move.
    Exact: produces bitwise the softmax of the full sequence up to f32
    accumulation order.
    """
    n = lax.axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    b, t, h, d = q.shape
    scale = scale or d ** -0.5
    qt = q.transpose(0, 2, 1, 3)
    pos_q = rank * t + jnp.arange(t)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(s, carry):
        o, m, l, kb, vb = carry
        src = (rank - s) % n  # which rank's block we currently hold
        pos_k = src * t + jnp.arange(t)
        o, m, l = _block_update(qt, kb, vb, o, m, l, pos_q, pos_k,
                                causal, scale)
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        return o, m, l, kb, vb

    # accumulators start replicated but the loop makes them device-varying;
    # pvary tells shard_map's vma type system up front
    vary = lambda x: lax.pcast(x, (axis_name,), to="varying")
    o0 = vary(jnp.zeros((b, h, t, d), jnp.float32))
    m0 = vary(jnp.full((b, h, t), _NEG))
    l0 = vary(jnp.zeros((b, h, t), jnp.float32))
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    o, _, l, _, _ = lax.fori_loop(0, n, body, (o0, m0, l0, kt, vt))
    return _finish(o, l, q.dtype).transpose(0, 2, 1, 3)


def ulysses_attention(q, k, v, axis_name: str, causal: bool = True,
                      scale: Optional[float] = None) -> jnp.ndarray:
    """All-to-all (DeepSpeed-Ulysses style) sequence parallelism.

    Under shard_map with sequence sharded [B, T_local, H, D]: one
    all_to_all re-shards to [B, T_global, H_local, D], full (flash-free)
    attention runs per local head subset, and a second all_to_all restores
    sequence sharding. H must be divisible by the axis size. Two
    all-to-alls move 2·B·T_local·H·D elements — less than the ring's
    rotating KV when heads are plentiful and N is small.
    """
    h = q.shape[2]
    n = lax.axis_size(axis_name)
    assert h % n == 0, f"heads {h} not divisible by axis size {n}"
    a2a = functools.partial(lax.all_to_all, axis_name=axis_name,
                            split_axis=2, concat_axis=1, tiled=True)
    qg, kg, vg = a2a(q), a2a(k), a2a(v)  # [B, T_glob, H_loc, D]
    og = full_attention(qg, kg, vg, causal=causal, scale=scale)
    return lax.all_to_all(og, axis_name=axis_name, split_axis=1,
                          concat_axis=2, tiled=True)


def make_ring_attention(mesh: Mesh, axis: str = "seq", causal: bool = True,
                        impl: str = "ring"):
    """Wrap the sharded attention for direct [B, T, H, D] arrays: shards T
    over `axis`, runs the chosen impl, returns the same layout."""
    inner = {"ring": ring_attention, "ulysses": ulysses_attention}[impl]

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(None, axis), P(None, axis), P(None, axis)),
        out_specs=P(None, axis))
    def fn(q, k, v):
        return inner(q, k, v, axis, causal=causal)

    return jax.jit(fn)
