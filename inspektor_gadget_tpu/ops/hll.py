"""HyperLogLog distinct-count sketch.

Role: distinct (saddr, daddr, dport) / distinct DNS qname counting
(BASELINE.md config 2) without per-key state. Update = scatter-max of leading
-zero ranks; merge = elementwise max (psum-able via jax.lax.pmax).

Standard 32-bit HLL (Flajolet et al.): p index bits, m=2^p registers,
alpha_m bias correction, linear counting below 2.5m, large-range correction
near 2^32.
"""

from __future__ import annotations

import flax.struct
import jax
import jax.numpy as jnp

from .hashing import fmix32


@flax.struct.dataclass
class HLL:
    registers: jnp.ndarray  # (m,) int32 — rank of max leading-zero run + 1
    p: int = flax.struct.field(pytree_node=False)


def hll_init(p: int = 14) -> HLL:
    return HLL(registers=jnp.zeros(1 << p, dtype=jnp.int32), p=p)


def _alpha(m: int) -> float:
    if m == 16:
        return 0.673
    if m == 32:
        return 0.697
    if m == 64:
        return 0.709
    return 0.7213 / (1 + 1.079 / m)


def hll_index_rank(keys: jnp.ndarray, p: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Each key's register (the hash's top p bits) and rank (leading zeros
    of the remaining 32-p bits, +1)."""
    h = fmix32(keys.astype(jnp.uint32))
    idx = (h >> (32 - p)).astype(jnp.int32)
    rest = (h << p) | jnp.uint32((1 << p) - 1)  # pad low bits so clz ≤ 32-p
    rank = jnp.clip(jax.lax.clz(rest.astype(jnp.int32)), 0, 32 - p) + 1
    return idx, rank.astype(jnp.int32)


def hll_update(state: HLL, keys: jnp.ndarray, mask: jnp.ndarray | None = None) -> HLL:
    idx, rank = hll_index_rank(keys, state.p)
    if mask is not None:
        rank = jnp.where(mask, rank, 0)
    return state.replace(registers=state.registers.at[idx].max(rank))


def hll_estimate(state: HLL) -> jnp.ndarray:
    m = state.registers.shape[0]
    regs = state.registers.astype(jnp.float32)
    raw = _alpha(m) * m * m / jnp.sum(jnp.exp2(-regs))
    zeros = jnp.sum(state.registers == 0).astype(jnp.float32)
    # small-range: linear counting when raw ≤ 2.5m and empty registers exist
    linear = m * jnp.log(m / jnp.maximum(zeros, 1.0))
    small = (raw <= 2.5 * m) & (zeros > 0)
    est = jnp.where(small, linear, raw)
    # large-range correction near 2^32
    two32 = jnp.float32(2.0**32)
    est = jnp.where(est > two32 / 30.0, -two32 * jnp.log1p(-est / two32), est)
    return est


def hll_merge(a: HLL, b: HLL) -> HLL:
    return a.replace(registers=jnp.maximum(a.registers, b.registers))


def hll_pmax(state: HLL, axis_name: str) -> HLL:
    """Cluster merge over a mesh axis — elementwise max all-reduce."""
    return state.replace(registers=jax.lax.pmax(state.registers, axis_name))
