"""Variational autoencoder anomaly scorer — the probabilistic alternative
to the deterministic AE (models/autoencoder.py).

Score = negative ELBO (reconstruction NLL + KL to the unit Gaussian), which
separates "rare but in-distribution" from "structurally novel" better than
plain reconstruction error on skewed syscall/flow distributions. Same
interface as the AE scorer, so the tpusketch operator can swap
(`anomaly-model=vae`). bf16 matmuls on the MXU; reparameterization keeps
the step jittable with an explicit PRNG key threaded through the state.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import flax.struct
import jax
import jax.numpy as jnp
import optax

from .autoencoder import normalize_counts


@dataclasses.dataclass(frozen=True)
class VAEConfig:
    input_dim: int = 4096
    hidden_dim: int = 512
    latent_dim: int = 64
    learning_rate: float = 1e-3
    kl_weight: float = 1e-2
    compute_dtype: Any = jnp.bfloat16


@flax.struct.dataclass
class VAEScorer:
    params: dict
    opt_state: Any
    rng: jnp.ndarray
    steps: jnp.ndarray
    config: VAEConfig = flax.struct.field(pytree_node=False)


def _optimizer(cfg: VAEConfig):
    return optax.adam(cfg.learning_rate)


def vae_init(cfg: VAEConfig = VAEConfig(), seed: int = 0) -> VAEScorer:
    k = jax.random.PRNGKey(seed)
    ks = jax.random.split(k, 8)

    def dense(key, fi, fo):
        return {
            "w": jax.random.normal(key, (fi, fo), jnp.float32) * jnp.sqrt(2.0 / fi),
            "b": jnp.zeros((fo,), jnp.float32),
        }

    params = {
        "enc": dense(ks[0], cfg.input_dim, cfg.hidden_dim),
        "mu": dense(ks[1], cfg.hidden_dim, cfg.latent_dim),
        "logvar": dense(ks[2], cfg.hidden_dim, cfg.latent_dim),
        "dec1": dense(ks[3], cfg.latent_dim, cfg.hidden_dim),
        "dec2": dense(ks[4], cfg.hidden_dim, cfg.input_dim),
    }
    return VAEScorer(params=params, opt_state=_optimizer(cfg).init(params),
                     rng=ks[5], steps=jnp.zeros((), jnp.int32), config=cfg)


def _layer(x, p, dt):
    return x.astype(dt) @ p["w"].astype(dt) + p["b"].astype(dt)


def vae_encode(params, x, cfg):
    h = jax.nn.gelu(_layer(x, params["enc"], cfg.compute_dtype))
    return (_layer(h, params["mu"], cfg.compute_dtype).astype(jnp.float32),
            _layer(h, params["logvar"], cfg.compute_dtype).astype(jnp.float32))


def vae_decode(params, z, cfg):
    h = jax.nn.gelu(_layer(z, params["dec1"], cfg.compute_dtype))
    return _layer(h, params["dec2"], cfg.compute_dtype).astype(jnp.float32)


def vae_elbo_terms(params, x, key, cfg):
    mu, logvar = vae_encode(params, x, cfg)
    logvar = jnp.clip(logvar, -8.0, 8.0)
    eps = jax.random.normal(key, mu.shape, jnp.float32)
    z = mu + jnp.exp(0.5 * logvar) * eps
    recon = vae_decode(params, z, cfg)
    rec_err = jnp.mean((recon - x) ** 2, axis=-1) * x.shape[-1]
    kl = -0.5 * jnp.sum(1 + logvar - mu**2 - jnp.exp(logvar), axis=-1)
    return rec_err, kl


def vae_loss(params, x, key, cfg):
    rec, kl = vae_elbo_terms(params, x, key, cfg)
    return jnp.mean(rec + cfg.kl_weight * kl)


def vae_score(scorer: VAEScorer, x: jnp.ndarray) -> jnp.ndarray:
    """Anomaly score = negative ELBO per row (deterministic: z = mu)."""
    cfg = scorer.config
    mu, logvar = vae_encode(scorer.params, x, cfg)
    logvar = jnp.clip(logvar, -8.0, 8.0)
    recon = vae_decode(scorer.params, mu, cfg)
    rec_err = jnp.mean((recon - x) ** 2, axis=-1) * x.shape[-1]
    kl = -0.5 * jnp.sum(1 + logvar - mu**2 - jnp.exp(logvar), axis=-1)
    return rec_err + cfg.kl_weight * kl


def vae_train_step(scorer: VAEScorer, x: jnp.ndarray,
                   axis_name: str | None = None) -> tuple[VAEScorer, jnp.ndarray]:
    key, next_rng = jax.random.split(scorer.rng)
    loss, grads = jax.value_and_grad(vae_loss)(scorer.params, x, key,
                                               scorer.config)
    if axis_name is not None:
        grads = jax.lax.pmean(grads, axis_name)
        loss = jax.lax.pmean(loss, axis_name)
    updates, opt_state = _optimizer(scorer.config).update(
        grads, scorer.opt_state, scorer.params)
    params = optax.apply_updates(scorer.params, updates)
    return scorer.replace(params=params, opt_state=opt_state, rng=next_rng,
                          steps=scorer.steps + 1), loss


@functools.partial(jax.jit, donate_argnums=0)
def anomaly_step(scorer: VAEScorer, counts: jnp.ndarray,
                 mask: jnp.ndarray) -> tuple[VAEScorer, jnp.ndarray]:
    """models/autoencoder.py `anomaly_step` for this scorer: normalise, one
    Adam step on the mean loss of the rows `mask` keeps, scores of the
    updated weights, as one program with the scorer donated. The noise is
    drawn for every row of the padded array, so a real row's draw depends
    on the padded shape (the same distribution, another sample)."""
    x = normalize_counts(counts)
    cfg = scorer.config
    key, next_rng = jax.random.split(scorer.rng)

    def loss(params):
        rec, kl = vae_elbo_terms(params, x, key, cfg)
        return (jnp.sum((rec + cfg.kl_weight * kl) * mask)
                / jnp.maximum(jnp.sum(mask), 1.0))

    grads = jax.grad(loss)(scorer.params)
    updates, opt_state = _optimizer(cfg).update(grads, scorer.opt_state,
                                                scorer.params)
    scorer = scorer.replace(params=optax.apply_updates(scorer.params, updates),
                            opt_state=opt_state, rng=next_rng,
                            steps=scorer.steps + 1)
    return scorer, vae_score(scorer, x)
