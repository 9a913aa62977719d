"""Expert parallelism: Switch-style top-1 routed mixture-of-experts.

The reference has no model parallelism of any kind (SURVEY.md §2.5 — its
distributed dimension is per-node fan-out only); expert parallelism is part
of this build's first-class TPU distributed plane, next to DP×TP
(parallel/cluster.py), sequence parallelism (models/seqmodel.py) and
pipeline parallelism (parallel/pipeline.py). The scorer families stay
small, but the routing/dispatch machinery is the real thing: the same
all_to_all schedule a production MoE uses, so the framework scales scorer
capacity by adding experts without growing per-token FLOPs.

TPU-first choices:
- Dense dispatch/combine einsums (one-hot matmuls) instead of scatter —
  static shapes, MXU-friendly, no data-dependent control flow under jit.
- Top-1 (Switch) routing with a fixed per-expert capacity; over-capacity
  tokens get a zero expert output (the caller's residual connection, as in
  models/seqmodel.py blocks, is what carries them through) — the standard
  bounded-memory trade, matching the framework's drop-accounting
  philosophy (every hop bounded, losses observable: the router reports a
  drop fraction).
- Expert parallelism via two `lax.all_to_all` hops over an 'expert' mesh
  axis inside shard_map: tokens→owning expert, expert outputs→token owner.
  With E experts over n ranks each device holds E/n expert FFNs; dispatch
  rides ICI.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

EXPERT_AXIS = "expert"


def moe_init(key, n_experts: int, d_model: int, d_ff: int) -> dict:
    """Router + stacked expert FFN params (experts on the leading axis, so
    sharding over the expert mesh axis is a single P('expert') spec)."""
    kg, k1, k2 = jax.random.split(key, 3)
    s1 = (2.0 / (d_model + d_ff)) ** 0.5
    return {
        "gate": jax.random.normal(kg, (d_model, n_experts), jnp.float32) * 0.02,
        "w1": jax.random.normal(k1, (n_experts, d_model, d_ff), jnp.float32) * s1,
        "b1": jnp.zeros((n_experts, d_ff), jnp.float32),
        "w2": jax.random.normal(k2, (n_experts, d_ff, d_model), jnp.float32) * s1,
        "b2": jnp.zeros((n_experts, d_model), jnp.float32),
    }


def moe_pspecs(expert_axis: str = EXPERT_AXIS) -> dict:
    """PartitionSpecs matching moe_init: experts sharded, router replicated."""
    return {
        "gate": P(),
        "w1": P(expert_axis), "b1": P(expert_axis),
        "w2": P(expert_axis), "b2": P(expert_axis),
    }


def _route(x: jnp.ndarray, gate_w: jnp.ndarray, capacity: int):
    """Top-1 routing → (dispatch [T,E,C], combine [T,E,C], aux) with static
    shapes. aux = (load-balance loss term, dropped-token fraction)."""
    t = x.shape[0]
    logits = x.astype(jnp.float32) @ gate_w
    probs = jax.nn.softmax(logits, axis=-1)              # [T, E]
    expert = jnp.argmax(probs, axis=-1)                  # [T]
    n_e = gate_w.shape[1]
    onehot = jax.nn.one_hot(expert, n_e, dtype=jnp.float32)
    gate = (probs * onehot).sum(-1)                      # chosen-expert prob
    # position of each token within its expert's capacity (exclusive cumsum)
    pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot    # [T, E]
    slot = pos.sum(-1)                                   # [T]
    keep = (slot < capacity).astype(jnp.float32)
    dispatch = (onehot * keep[:, None])[:, :, None] * jax.nn.one_hot(
        jnp.clip(slot, 0, capacity - 1).astype(jnp.int32), capacity,
        dtype=jnp.float32)[:, None, :]                   # [T, E, C]
    combine = dispatch * gate[:, None, None]
    # Switch load-balance loss: E * sum_e(frac_tokens_e * mean_prob_e)
    frac = onehot.mean(0)
    balance = n_e * jnp.sum(frac * probs.mean(0))
    dropped = 1.0 - keep.mean() if t else jnp.float32(0.0)
    return dispatch, combine, (balance, dropped)


def _expert_ffn(w1, b1, w2, b2, h):
    """Apply stacked expert FFNs: h [E, C, d] → [E, C, d] (bf16 matmuls)."""
    z = jnp.einsum("ecd,edf->ecf", h.astype(jnp.bfloat16),
                   w1.astype(jnp.bfloat16)) + b1[:, None, :].astype(jnp.bfloat16)
    z = jax.nn.gelu(z)
    out = jnp.einsum("ecf,efd->ecd", z, w2.astype(jnp.bfloat16))
    return out.astype(jnp.float32) + b2[:, None, :]


def moe_apply(params: dict, x: jnp.ndarray,
              capacity_factor: float = 2.0) -> tuple[jnp.ndarray, tuple]:
    """Single-device reference MoE: x [T, d] → ([T, d], aux). All experts
    local; the EP path must produce identical outputs (tests enforce it),
    so both are the same moe_ff code path."""
    return moe_ff(params, x, capacity_factor)


def moe_ff(params: dict, x: jnp.ndarray, capacity_factor: float = 2.0,
           axis_name: str | None = None,
           axis_size: int = 1) -> tuple[jnp.ndarray, tuple]:
    """Routed FF usable as a drop-in for a dense FF block: x [T, d] →
    (y [T, d], (balance_loss, drop_frac)). With `axis_name` set (inside
    shard_map over the expert axis), experts are sharded and dispatch takes
    the two all_to_all hops; otherwise all experts are local. This is the
    building block models embed (models/seqmodel.py MoE layers);
    make_ep_moe wraps it as a standalone jitted fn."""
    t = x.shape[0]
    n_experts = params["gate"].shape[1]  # gate is replicated, global width
    if axis_name and params["w1"].shape[0] * axis_size != n_experts:
        raise ValueError(
            f"expert shard {params['w1'].shape[0]} × axis {axis_size} != "
            f"gate width {n_experts}")
    capacity = max(1, int(t / n_experts * capacity_factor))
    dispatch, combine, (bal, drop) = _route(x, params["gate"], capacity)
    h = jnp.einsum("tec,td->ecd", dispatch, x.astype(jnp.float32))
    if axis_name:
        h = lax.all_to_all(h, axis_name, split_axis=0, concat_axis=1,
                           tiled=True)
    out = _expert_ffn(params["w1"], params["b1"], params["w2"], params["b2"], h)
    if axis_name:
        out = lax.all_to_all(out, axis_name, split_axis=1, concat_axis=0,
                             tiled=True)
    y = jnp.einsum("tec,ecd->td", combine, out).astype(x.dtype)
    return y, (bal, drop)


def make_ep_moe(mesh: Mesh, n_experts: int, capacity_factor: float = 2.0,
                axis: str = EXPERT_AXIS):
    """Build the expert-parallel MoE: tokens [T, d] sharded over `axis`,
    experts sharded over `axis` (E/n per device), two all_to_all hops.

    Returns a jitted fn(params, x) → (y, (balance_loss, drop_frac)).
    """
    n = mesh.shape[axis]
    if n_experts % n:
        raise ValueError(f"n_experts={n_experts} not divisible by mesh axis {n}")

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(moe_pspecs(axis), P(axis)),
        out_specs=(P(axis), (P(), P())))
    def ep(params, x):
        # local dispatch over ALL experts, then two all_to_all hops:
        # tokens → owning expert shard, expert outputs → token owner
        y, (bal, drop) = moe_ff(params, x, capacity_factor,
                                axis_name=axis, axis_size=n)
        return y, (lax.pmean(bal, axis), lax.pmean(drop, axis))

    return jax.jit(ep)
