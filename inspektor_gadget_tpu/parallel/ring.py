"""Ring collectives for sketch merging.

Why a ring here: `jax.lax.psum` is the right default for the ≤ ~1.3MB
sketch bundles (XLA already emits near-optimal all-reduces on ICI). But
cross-slice merges of *wide* CMS tables (depth × 2^20+ counters for
long-horizon retention) are bandwidth-bound on DCN, and a hand-rolled ring
lets the runtime overlap each hop with the next ingest step and chunk the
table so per-hop messages stay under the DCN sweet spot — the same reason
ring attention passes KV blocks hop-by-hop instead of all-gathering them.

ring_psum: N-1 ppermute hops, each adding the neighbor's shard-sum;
ring_psum_chunked: the bidirectional variant splitting the table into
per-hop chunks (reduce-scatter + all-gather schedule).
Both are exact (integer tables: addition is associative; order-safe).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def ring_psum(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """All-reduce via N-1 ring hops of the full tensor (exact for ints)."""
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(i, carry):
        acc, buf = carry
        buf = jax.lax.ppermute(buf, axis_name, perm)
        return acc + buf, buf

    acc, _ = jax.lax.fori_loop(0, n - 1, body, (x, x))
    del idx
    return acc


def ring_psum_chunked(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Reduce-scatter + all-gather ring schedule (bandwidth-optimal
    2(N-1)/N of the naive ring): the tensor is split into N chunks; each
    rank reduces one chunk over N-1 hops, then the reduced chunks ride
    N-1 more hops to every rank."""
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    orig_shape = x.shape
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros(pad, flat.dtype)])
    chunks = flat.reshape(n, -1)
    rank = jax.lax.axis_index(axis_name)
    send_next = [(i, (i + 1) % n) for i in range(n)]

    # reduce-scatter: after step s, rank r holds the partial sum of chunk
    # (r - s) mod n accumulated over s+1 ranks
    def rs_body(s, state):
        chunks, send = state
        recv = jax.lax.ppermute(send, axis_name, send_next)
        idx = (rank - s - 1) % n
        updated = jax.lax.dynamic_index_in_dim(chunks, idx, 0, keepdims=False) + recv
        chunks = jax.lax.dynamic_update_index_in_dim(chunks, updated, idx, 0)
        return chunks, updated

    first_send = jax.lax.dynamic_index_in_dim(chunks, rank % n, 0, keepdims=False)
    chunks, _ = jax.lax.fori_loop(0, n - 1, rs_body, (chunks, first_send))

    # all-gather: circulate each fully reduced chunk
    def ag_body(s, state):
        chunks, send = state
        recv = jax.lax.ppermute(send, axis_name, send_next)
        idx = (rank - s) % n
        chunks = jax.lax.dynamic_update_index_in_dim(chunks, recv, idx, 0)
        return chunks, recv

    own = jax.lax.dynamic_index_in_dim(chunks, (rank + 1) % n, 0, keepdims=False)
    chunks, _ = jax.lax.fori_loop(0, n - 1, ag_body, (chunks, own))

    out = chunks.reshape(-1)
    if pad:
        out = out[:-pad]
    return out.reshape(orig_shape)
