"""Pipeline parallelism: GPipe microbatch schedule over a 'stage' mesh axis.

The reference has no model parallelism (SURVEY.md §2.5); this completes the
framework's distributed plane (dp/tp/sp/ep/pp) so deep scorer stacks can be
sliced layer-wise across chips when a model no longer fits (or batches are
latency-bound) on one.

Design — idiomatic XLA, no host control flow:
- The model is S identical residual blocks; params are stacked on a leading
  stage axis and sharded P('stage'), so each device holds exactly its
  block(s). Layer-stacking + scan is the standard JAX pipelining shape.
- Inside shard_map, a single `lax.scan` runs S + M - 1 ticks (M =
  microbatches). Each tick: stage 0 injects the next microbatch, every
  stage applies its block, then one `lax.ppermute` hop shifts activations
  to the next stage — the classic bubble-fill/drain schedule with static
  shapes throughout.
- The last stage accumulates outputs; a masked psum broadcasts the result
  (tiny shapes here; a production variant would reduce_scatter).
- The whole schedule is differentiable: `make_pp_train_step` grads through
  the scan; each stage ends up with grads only for its own (sharded) block
  params, while the replicated head is trained outside shard_map.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

STAGE_AXIS = "stage"


def pp_block_init(key, n_stages: int, d_model: int, d_ff: int) -> dict:
    """S stacked residual MLP blocks: leading axis = pipeline stage."""
    k1, k2 = jax.random.split(key)
    s = (2.0 / (d_model + d_ff)) ** 0.5
    return {
        "w1": jax.random.normal(k1, (n_stages, d_model, d_ff), jnp.float32) * s,
        "b1": jnp.zeros((n_stages, d_ff), jnp.float32),
        "w2": jax.random.normal(k2, (n_stages, d_ff, d_model), jnp.float32) * s,
        "b2": jnp.zeros((n_stages, d_model), jnp.float32),
    }


def pp_pspecs(axis: str = STAGE_AXIS) -> dict:
    return {"w1": P(axis), "b1": P(axis), "w2": P(axis), "b2": P(axis)}


def _block(p, x):
    """One residual MLP block; p carries a leading local-stage axis of 1."""
    w1, b1 = p["w1"][0], p["b1"][0]
    w2, b2 = p["w2"][0], p["b2"][0]
    h = jax.nn.gelu(x.astype(jnp.bfloat16) @ w1.astype(jnp.bfloat16) + b1.astype(jnp.bfloat16))
    return x + (h @ w2.astype(jnp.bfloat16)).astype(jnp.float32) + b2


def pp_reference(params: dict, x: jnp.ndarray) -> jnp.ndarray:
    """Sequential single-device forward (ground truth for the pipeline)."""
    def body(h, p):
        return _block(jax.tree.map(lambda a: a[None], p), h), None
    out, _ = lax.scan(body, x, params)
    return out


def make_pp_forward(mesh: Mesh, axis: str = STAGE_AXIS):
    """Pipelined forward: x [M, mb, d] (microbatches, replicated in),
    result [M, mb, d] (replicated out)."""
    s = mesh.shape[axis]
    perm = [(i, (i + 1) % s) for i in range(s)]

    # check_vma=False: the scan carry mixes varying and replicated
    # values; the schedule itself is checked by the numerics tests
    # (pp_forward == sequential)
    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(pp_pspecs(axis), P()), out_specs=P(),
                       check_vma=False)
    def fwd(params, x):
        stage = lax.axis_index(axis)
        m, mb, d = x.shape

        def tick(carry, t):
            act, outbuf = carry
            inj = lax.dynamic_index_in_dim(x, jnp.clip(t, 0, m - 1), 0,
                                           keepdims=False)
            act = jnp.where(stage == 0, inj, act)
            out = _block(params, act)
            oidx = t - (s - 1)
            write = (stage == s - 1) & (oidx >= 0)
            outbuf = lax.dynamic_update_index_in_dim(
                outbuf,
                jnp.where(write, out, lax.dynamic_index_in_dim(
                    outbuf, jnp.clip(oidx, 0, m - 1), 0, keepdims=False)),
                jnp.clip(oidx, 0, m - 1), 0)
            act = lax.ppermute(out, axis, perm)
            return (act, outbuf), None

        init = jax.tree.map(
            lambda a: lax.pcast(a, (axis,), to="varying"),
            (jnp.zeros((mb, d), jnp.float32), jnp.zeros_like(x)))
        (_, outbuf), _ = lax.scan(tick, init, jnp.arange(m + s - 1))
        # only the last stage holds real outputs; broadcast via masked psum
        return lax.psum(jnp.where(stage == s - 1, outbuf, 0.0), axis)

    return jax.jit(fwd)


def make_pp_train_step(mesh: Mesh, lr: float = 1e-3, axis: str = STAGE_AXIS):
    """Jitted pipeline-parallel train step on (stacked blocks + replicated
    linear head): MSE to targets, SGD update. Grads for block params stay
    stage-local (they are sharded); the head runs on the replicated
    pipeline output outside shard_map, so its grad needs no reduction."""
    fwd_inner = make_pp_forward(mesh, axis)

    def loss_fn(params, head, x, y):
        h = fwd_inner(params, x)
        pred = h @ head
        return jnp.mean((pred - y) ** 2)

    @jax.jit
    def step(params, head, x, y):
        loss, (gp, gh) = jax.value_and_grad(loss_fn, argnums=(0, 1))(
            params, head, x, y)
        params = jax.tree.map(lambda p, g: p - lr * g, params, gp)
        head = head - lr * gh
        return params, head, loss

    return step
