"""Autoencoder anomaly scorer over per-container event distributions.

Input: L1-normalized, log-scaled count vectors (e.g. the 2^12-bucket syscall
distribution from the entropy sketch, per container). A 3-layer MLP
autoencoder reconstructs the vector; per-row MSE is the anomaly score.
Online training: Adam on streaming mini-batches; weights replicate across
the mesh, gradients psum over the 'node' axis (pure DP — the vectors are
tiny; the matmuls batch onto the MXU in bf16).

TPU notes: params kept in f32, activations cast to bf16 for the matmuls;
hidden sizes padded to multiples of 128 (MXU lane width).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import flax.struct
import jax
import jax.numpy as jnp
import optax


@dataclasses.dataclass(frozen=True)
class AEConfig:
    input_dim: int = 4096        # matches entropy sketch width (2^12)
    hidden_dim: int = 512
    latent_dim: int = 128
    learning_rate: float = 1e-3
    compute_dtype: Any = jnp.bfloat16


@flax.struct.dataclass
class AnomalyScorer:
    params: dict
    opt_state: Any
    steps: jnp.ndarray
    config: AEConfig = flax.struct.field(pytree_node=False)


def _optimizer(cfg: AEConfig):
    return optax.adam(cfg.learning_rate)


def ae_init(cfg: AEConfig = AEConfig(), seed: int = 0) -> AnomalyScorer:
    k = jax.random.PRNGKey(seed)
    ks = jax.random.split(k, 6)

    def dense(key, fan_in, fan_out):
        scale = jnp.sqrt(2.0 / fan_in)
        return {
            "w": jax.random.normal(key, (fan_in, fan_out), jnp.float32) * scale,
            "b": jnp.zeros((fan_out,), jnp.float32),
        }

    params = {
        "enc1": dense(ks[0], cfg.input_dim, cfg.hidden_dim),
        "enc2": dense(ks[1], cfg.hidden_dim, cfg.latent_dim),
        "dec1": dense(ks[2], cfg.latent_dim, cfg.hidden_dim),
        "dec2": dense(ks[3], cfg.hidden_dim, cfg.input_dim),
    }
    opt_state = _optimizer(cfg).init(params)
    return AnomalyScorer(params=params, opt_state=opt_state,
                         steps=jnp.zeros((), jnp.int32), config=cfg)


def _layer(x, p, dtype):
    return x.astype(dtype) @ p["w"].astype(dtype) + p["b"].astype(dtype)


def ae_apply(params: dict, x: jnp.ndarray, cfg: AEConfig) -> jnp.ndarray:
    dt = cfg.compute_dtype
    h = jax.nn.gelu(_layer(x, params["enc1"], dt))
    z = jax.nn.gelu(_layer(h, params["enc2"], dt))
    h = jax.nn.gelu(_layer(z, params["dec1"], dt))
    out = _layer(h, params["dec2"], dt)
    return out.astype(jnp.float32)


def normalize_counts(counts: jnp.ndarray) -> jnp.ndarray:
    """log1p + L1 normalize a (batch, dim) count matrix."""
    x = jnp.log1p(counts.astype(jnp.float32))
    return x / jnp.maximum(x.sum(axis=-1, keepdims=True), 1e-6)


def ae_loss(params: dict, x: jnp.ndarray, cfg: AEConfig) -> jnp.ndarray:
    recon = ae_apply(params, x, cfg)
    return jnp.mean((recon - x) ** 2)


def ae_score(scorer: AnomalyScorer, x: jnp.ndarray) -> jnp.ndarray:
    """Per-row anomaly score: reconstruction MSE, scaled for display."""
    recon = ae_apply(scorer.params, x, scorer.config)
    return jnp.mean((recon - x) ** 2, axis=-1) * x.shape[-1]


def ae_train_step(
    scorer: AnomalyScorer, x: jnp.ndarray, axis_name: str | None = None
) -> tuple[AnomalyScorer, jnp.ndarray]:
    """One Adam step; grads psum'd over `axis_name` when run under shard_map
    (data-parallel over the node axis of the mesh)."""
    loss, grads = jax.value_and_grad(ae_loss)(scorer.params, x, scorer.config)
    if axis_name is not None:
        grads = jax.lax.pmean(grads, axis_name)
        loss = jax.lax.pmean(loss, axis_name)
    updates, opt_state = _optimizer(scorer.config).update(grads, scorer.opt_state, scorer.params)
    params = optax.apply_updates(scorer.params, updates)
    return scorer.replace(params=params, opt_state=opt_state, steps=scorer.steps + 1), loss


@functools.partial(jax.jit, donate_argnums=0)
def anomaly_step(scorer: AnomalyScorer, counts: jnp.ndarray,
                 mask: jnp.ndarray) -> tuple[AnomalyScorer, jnp.ndarray]:
    """What a harvest asks of the scorer, as one program: `normalize_counts`,
    one Adam step, and the scores of the updated weights. `counts` is the
    operator's `[slots, dim]` array, a power of two of rows, and `mask`
    (`[slots]`, 1.0 where a slot holds a container) keeps the filler rows
    out of the loss, so the shape changes only when the slots double. The
    loss is the mean over the real rows of their mean squared error, which
    is `ae_loss` of those rows alone; a filler row's score is computed and
    is the caller's to drop. The scorer is donated: the caller swaps it
    under the lock its other readers take."""
    x = normalize_counts(counts)
    cfg = scorer.config

    def loss(params):
        err = jnp.mean((ae_apply(params, x, cfg) - x) ** 2, axis=-1)
        return jnp.sum(err * mask) / jnp.maximum(jnp.sum(mask), 1.0)

    grads = jax.grad(loss)(scorer.params)
    updates, opt_state = _optimizer(cfg).update(grads, scorer.opt_state,
                                                scorer.params)
    scorer = scorer.replace(params=optax.apply_updates(scorer.params, updates),
                            opt_state=opt_state, steps=scorer.steps + 1)
    return scorer, ae_score(scorer, x)


# ---------------------------------------------------------------------------
# Tensor-parallel variant (Megatron MLP pattern over the mesh 'model' axis):
# enc1/dec1 column-parallel (hidden sharded, no collective), enc2/dec2
# row-parallel (contract over the sharded hidden → one psum each). Two
# psums per forward; activations stay sharded through the gelu.
# ---------------------------------------------------------------------------


def ae_param_pspecs(model_axis: str = "model"):
    """PartitionSpec tree for tensor-parallel autoencoder params."""
    from jax.sharding import PartitionSpec as P

    col = {"w": P(None, model_axis), "b": P(model_axis)}   # column-parallel
    row = {"w": P(model_axis, None), "b": P()}             # row-parallel
    return {"enc1": col, "enc2": row, "dec1": col, "dec2": row}


def ae_apply_tp(params: dict, x: jnp.ndarray, cfg: AEConfig,
                model_axis: str = "model") -> jnp.ndarray:
    dt = cfg.compute_dtype
    h = jax.nn.gelu(_layer(x, params["enc1"], dt))          # (b, hidden/m)
    z = jax.lax.psum(
        (h.astype(dt) @ params["enc2"]["w"].astype(dt)), model_axis
    ) + params["enc2"]["b"].astype(dt)
    z = jax.nn.gelu(z)                                      # (b, latent) repl
    h2 = jax.nn.gelu(_layer(z, params["dec1"], dt))         # (b, hidden/m)
    out = jax.lax.psum(
        (h2.astype(dt) @ params["dec2"]["w"].astype(dt)), model_axis
    ) + params["dec2"]["b"].astype(dt)
    return out.astype(jnp.float32)


def ae_loss_tp(params: dict, x: jnp.ndarray, cfg: AEConfig,
               model_axis: str = "model") -> jnp.ndarray:
    recon = ae_apply_tp(params, x, cfg, model_axis)
    return jnp.mean((recon - x) ** 2)


def ae_score_tp(scorer: AnomalyScorer, x: jnp.ndarray,
                model_axis: str = "model") -> jnp.ndarray:
    recon = ae_apply_tp(scorer.params, x, scorer.config, model_axis)
    return jnp.mean((recon - x) ** 2, axis=-1) * x.shape[-1]


def ae_train_step_tp(
    scorer: AnomalyScorer, x: jnp.ndarray, *, dp_axis: str | None = "node",
    model_axis: str = "model",
) -> tuple[AnomalyScorer, jnp.ndarray]:
    """DP×TP step under shard_map: forward/backward with model-axis psums
    (autodiff transposes them correctly), grads pmean'd over the data axis,
    per-shard Adam update (optimizer state shards like the params)."""
    loss, grads = jax.value_and_grad(ae_loss_tp)(
        scorer.params, x, scorer.config, model_axis)
    if dp_axis is not None:
        grads = jax.lax.pmean(grads, dp_axis)
        loss = jax.lax.pmean(loss, dp_axis)
    updates, opt_state = _optimizer(scorer.config).update(
        grads, scorer.opt_state, scorer.params)
    params = optax.apply_updates(scorer.params, updates)
    return scorer.replace(params=params, opt_state=opt_state,
                          steps=scorer.steps + 1), loss
