"""Sequence anomaly scorer: a causal transformer LM over syscall tokens.

Third scorer family next to the autoencoder (autoencoder.py) and VAE
(vae.py). Where those score per-container *distributions* (bag of
syscalls), this one scores *order*: the model is trained online as a
next-token LM over each container's recent event-key sequence, and the
anomaly score is the mean next-token negative log-likelihood — a container
doing familiar things in an unfamiliar order lights up here and nowhere
else. Reference analogue: the `advise seccomp-profile` gadget's per-
container syscall recording (reference pkg/gadget-collection/gadgets/
advise/seccomp/gadget.go:582) — which only captures the *set*; this is
the TPU-native upgrade to full sequence likelihood.

TPU-first choices: bf16 matmuls (MXU), f32 softmax/layernorm state,
sinusoidal positions (no learned table → any window length, and under
sequence parallelism each shard derives its global positions locally),
attention backend selectable per call: 'full' (short windows),
'blockwise' (long windows, one chip), 'flash' (Pallas fused kernel with a
blockwise-recompute custom_vjp — fastest long-window path for scoring and
training, parallel/flash_attention.py), 'ring' / 'ulysses' (windows
sharded over a mesh axis — parallel/ring_attention.py).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..parallel.flash_attention import flash_attention
from ..parallel.ring_attention import (
    blockwise_attention, full_attention, ring_attention, ulysses_attention,
)

# backends rejected by training entry points (currently none: 'flash'
# carries a custom_vjp — fused forward, blockwise-recompute backward)
_SCORE_ONLY_ATTN: frozenset = frozenset()


def _check_trainable_attn(attn: str) -> None:
    if attn in _SCORE_ONLY_ATTN:
        raise ValueError(
            f"attn={attn!r} is a score-only backend; train with 'full', "
            "'blockwise', 'ring' or 'ulysses'")


@dataclasses.dataclass(frozen=True)
class SeqConfig:
    vocab: int = 512          # syscall/key token space (key % vocab)
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 256
    lr: float = 1e-3
    dtype: Any = jnp.bfloat16
    # MoE FF (0 = dense): scorer capacity scales by adding experts without
    # growing per-token FLOPs; experts shard over an 'expert' mesh axis via
    # make_ep_train_step (parallel/moe.py all_to_all dispatch)
    n_experts: int = 0
    capacity_factor: float = 2.0
    balance_coef: float = 0.01


@dataclasses.dataclass
class SeqScorer:
    params: dict
    opt_state: Any
    steps: int
    config: SeqConfig


def _optimizer(cfg: SeqConfig):
    return optax.adamw(cfg.lr)


def seq_init(cfg: SeqConfig = SeqConfig(), seed: int = 0) -> SeqScorer:
    k = jax.random.PRNGKey(seed)
    keys = iter(jax.random.split(k, 4 + 8 * cfg.n_layers))

    def dense(fi, fo):
        return {
            "w": (jax.random.normal(next(keys), (fi, fo), jnp.float32)
                  * (2.0 / (fi + fo)) ** 0.5),
            "b": jnp.zeros((fo,), jnp.float32),
        }

    d, f = cfg.d_model, cfg.d_ff
    layers = []
    for _ in range(cfg.n_layers):
        layer = {
            "ln1": {"g": jnp.ones((d,)), "b": jnp.zeros((d,))},
            "qkv": dense(d, 3 * d),
            "out": dense(d, d),
            "ln2": {"g": jnp.ones((d,)), "b": jnp.zeros((d,))},
        }
        if cfg.n_experts:
            from ..parallel.moe import moe_init
            layer["moe"] = moe_init(next(keys), cfg.n_experts, d, f)
        else:
            layer["ff1"] = dense(d, f)
            layer["ff2"] = dense(f, d)
        layers.append(layer)
    params = {
        "embed": jax.random.normal(next(keys), (cfg.vocab, d)) * 0.02,
        "layers": layers,
        "lnf": {"g": jnp.ones((d,)), "b": jnp.zeros((d,))},
        "unembed": dense(d, cfg.vocab),
    }
    return SeqScorer(params=params, opt_state=_optimizer(cfg).init(params),
                     steps=0, config=cfg)


def _ln(x, p):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    return ((x32 - mu) * lax.rsqrt(var + 1e-6) * p["g"] + p["b"]).astype(x.dtype)


def _dense(x, p):
    return x @ p["w"].astype(x.dtype) + p["b"].astype(x.dtype)


def _sincos_positions(pos, d):
    """Sinusoidal encoding for explicit (possibly shard-offset) positions."""
    half = d // 2
    freqs = jnp.exp(-jnp.arange(half) * (jnp.log(10000.0) / max(half - 1, 1)))
    ang = pos[:, None].astype(jnp.float32) * freqs[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _attend(q, k, v, cfg, attn: str, axis_name: str | None):
    if attn == "full":
        return full_attention(q, k, v, causal=True)
    if attn == "flash":
        return flash_attention(q, k, v, causal=True)
    if attn == "blockwise":
        t = q.shape[1]
        chunk = next(c for c in range(min(128, t), 0, -1) if t % c == 0)
        return blockwise_attention(q, k, v, causal=True, chunk=chunk)
    if attn == "ring":
        return ring_attention(q, k, v, axis_name, causal=True)
    if attn == "ulysses":
        return ulysses_attention(q, k, v, axis_name, causal=True)
    raise ValueError(f"unknown attention impl {attn!r}")


def _seq_apply_aux(params: dict, tokens: jnp.ndarray, cfg: SeqConfig,
                   attn: str = "full", axis_name: str | None = None,
                   pos_offset: jnp.ndarray | int = 0,
                   ep_axis: str | None = None,
                   ep_size: int = 1) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(logits [B,T,vocab], moe balance loss) — internal; ep_axis routes MoE
    layers through the expert-parallel all_to_all path inside shard_map."""
    b, t = tokens.shape
    d, h = cfg.d_model, cfg.n_heads
    pos = pos_offset + jnp.arange(t)
    x = (params["embed"][tokens] + _sincos_positions(pos, d)).astype(cfg.dtype)
    balance = jnp.float32(0.0)
    for lp in params["layers"]:
        y = _ln(x, lp["ln1"])
        qkv = _dense(y, lp["qkv"]).reshape(b, t, 3, h, d // h)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        a = _attend(q, k, v, cfg, attn, axis_name).reshape(b, t, d)
        x = x + _dense(a, lp["out"])
        y = _ln(x, lp["ln2"])
        if "moe" in lp:
            from ..parallel.moe import moe_ff
            ff, (bal, _) = moe_ff(lp["moe"], y.reshape(b * t, d),
                                  cfg.capacity_factor, axis_name=ep_axis,
                                  axis_size=ep_size)
            x = x + ff.reshape(b, t, d)
            balance = balance + bal
        else:
            x = x + _dense(jax.nn.gelu(_dense(y, lp["ff1"])), lp["ff2"])
    x = _ln(x, params["lnf"])
    return _dense(x, params["unembed"]).astype(jnp.float32), balance


def seq_apply(params: dict, tokens: jnp.ndarray, cfg: SeqConfig,
              attn: str = "full", axis_name: str | None = None,
              pos_offset: jnp.ndarray | int = 0) -> jnp.ndarray:
    """Logits [B, T, vocab] for token ids [B, T] (int32).

    Under sequence parallelism, `tokens` is the local shard and
    `pos_offset` the global index of its first column.
    """
    return _seq_apply_aux(params, tokens, cfg, attn, axis_name, pos_offset)[0]


def _token_nll(logits: jnp.ndarray, targets: jnp.ndarray,
               mask: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-sequence (sum NLL, count) over masked next-token targets."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    nll = nll * mask
    return nll.sum(axis=-1), mask.sum(axis=-1)


def seq_loss(params: dict, tokens: jnp.ndarray, cfg: SeqConfig,
             attn: str = "full") -> jnp.ndarray:
    logits, bal = _seq_apply_aux(params, tokens[:, :-1], cfg, attn=attn)
    mask = (tokens[:, 1:] >= 0).astype(jnp.float32)
    s, c = _token_nll(logits, jnp.maximum(tokens[:, 1:], 0), mask)
    return s.sum() / jnp.maximum(c.sum(), 1.0) + cfg.balance_coef * bal


@functools.partial(jax.jit, static_argnames=("cfg", "attn"), donate_argnums=(0, 1))
def _train_step(params, opt_state, tokens, cfg: SeqConfig, attn: str):
    loss, grads = jax.value_and_grad(seq_loss)(params, tokens, cfg, attn)
    updates, opt_state = _optimizer(cfg).update(grads, opt_state, params)
    return optax.apply_updates(params, updates), opt_state, loss


def seq_train_step(scorer: SeqScorer, tokens: jnp.ndarray,
                   attn: str = "full") -> tuple[SeqScorer, jnp.ndarray]:
    _check_trainable_attn(attn)
    p, o, loss = _train_step(scorer.params, scorer.opt_state, tokens,
                             scorer.config, attn)
    return SeqScorer(params=p, opt_state=o, steps=scorer.steps + 1,
                     config=scorer.config), loss


@functools.partial(jax.jit, static_argnames=("cfg", "attn"))
def _score(params, tokens, cfg: SeqConfig, attn: str):
    logits = seq_apply(params, tokens[:, :-1], cfg, attn=attn)
    mask = (tokens[:, 1:] >= 0).astype(jnp.float32)
    s, c = _token_nll(logits, jnp.maximum(tokens[:, 1:], 0), mask)
    return s / jnp.maximum(c, 1.0)


def seq_score(scorer: SeqScorer, tokens: jnp.ndarray,
              attn: str = "full") -> jnp.ndarray:
    """Mean next-token NLL per sequence — the anomaly score. Padding is
    marked with negative token ids."""
    return _score(scorer.params, tokens, scorer.config, attn)


# --- sequence-parallel training (long windows sharded over a mesh axis) ----

def _sp_loss_local(params, tok_local, rank, n, cfg, attn, axis_name):
    """Local-shard loss body under shard_map. Next-token targets cross the
    shard boundary: each rank fetches the *first* token of the next rank's
    shard via one ppermute hop; the final global position has no target."""
    b, t = tok_local.shape
    logits = seq_apply(params, tok_local, cfg, attn=attn,
                       axis_name=axis_name, pos_offset=rank * t)
    nxt_first = lax.ppermute(tok_local[:, 0], axis_name,
                             [(i, (i - 1) % n) for i in range(n)])
    targets = jnp.concatenate([tok_local[:, 1:], nxt_first[:, None]], axis=1)
    mask = (targets >= 0).astype(jnp.float32)
    mask = mask.at[:, -1].set(jnp.where(rank == n - 1, 0.0, mask[:, -1]))
    s, c = _token_nll(logits, jnp.maximum(targets, 0), mask)
    return (lax.psum(s.sum(), axis_name),
            lax.psum(c.sum(), axis_name))


def make_sp_train_step(mesh: Mesh, cfg: SeqConfig, attn: str = "ring",
                       axis: str = "seq"):
    """Build a jitted sequence-parallel train step: tokens [B, T_global]
    sharded over `axis`, params replicated, grads psum-reduced."""
    _check_trainable_attn(attn)
    n = mesh.shape[axis]
    opt = _optimizer(cfg)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(), P(), P(None, axis)),
        out_specs=(P(), P(), P()))
    def step(params, opt_state, tokens):
        rank = lax.axis_index(axis)

        def loss_fn(p):
            s, c = _sp_loss_local(p, tokens, rank, n, cfg, attn, axis)
            return s / jnp.maximum(c, 1.0)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        # loss_fn is already the *global* loss (psum'd numerator/denominator),
        # so each rank's grad holds only its local terms: sum, don't average.
        grads = jax.tree.map(lambda g: lax.psum(g, axis), grads)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return jax.jit(step, donate_argnums=(0, 1))


# --- expert-parallel training (MoE layers sharded over an 'expert' axis) ---

def seq_param_pspecs(params: dict, ep_axis: str):
    """PartitionSpecs for a MoE seq model: expert FFN stacks sharded on
    their leading expert dim, everything else (embed, attention, gate,
    norms) replicated — the standard DP+EP-on-one-axis layout."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _leaf: P(ep_axis) if _is_expert_path(path) else P(),
        params)


def _is_expert_path(path) -> bool:
    keys = [getattr(p, "key", getattr(p, "name", "")) for p in path]
    return "moe" in keys and keys[-1] != "gate"


def make_ep_train_step(mesh: Mesh, cfg: SeqConfig, scorer: SeqScorer,
                       attn: str = "full", axis: str = "expert"):
    """Build a jitted expert-parallel train step for a MoE seq scorer:
    token batches [B, T] sharded over `axis` (data parallel), MoE expert
    stacks sharded over the same axis (expert parallel — the layers take
    the all_to_all dispatch path), dense params replicated with psum'd
    grads. Expert grads need no reduction: the all_to_all backprop already
    delivers every rank's contribution to the owning shard. `scorer` is
    only used as the tree template for partition specs."""
    _check_trainable_attn(attn)
    if not cfg.n_experts:
        raise ValueError("make_ep_train_step requires cfg.n_experts > 0")
    n = mesh.shape[axis]
    if cfg.n_experts % n:
        raise ValueError(f"n_experts={cfg.n_experts} not divisible by {n}")
    opt = _optimizer(cfg)

    pspecs = seq_param_pspecs(scorer.params, axis)
    # optimizer state embeds copies of the param tree per moment; the same
    # path rule shards expert moments and replicates the rest + scalars
    ospecs = seq_param_pspecs(scorer.opt_state, axis)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(pspecs, ospecs, P(axis)),
        out_specs=(pspecs, ospecs, P()))
    def step(params, opt_state, tokens):
        def loss_fn(p):
            logits, bal = _seq_apply_aux(
                p, tokens[:, :-1], cfg, attn=attn, ep_axis=axis, ep_size=n)
            mask = (tokens[:, 1:] >= 0).astype(jnp.float32)
            s, c = _token_nll(logits, jnp.maximum(tokens[:, 1:], 0), mask)
            nll = (lax.psum(s.sum(), axis)
                   / jnp.maximum(lax.psum(c.sum(), axis), 1.0))
            return nll + cfg.balance_coef * lax.pmean(bal, axis)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        # replicated leaves: sum local grad terms across ranks; expert
        # shards: already complete on their owner (see docstring)
        grads = jax.tree_util.tree_map_with_path(
            lambda path, g: g if _is_expert_path(path) else lax.psum(g, axis),
            grads)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return jax.jit(step, donate_argnums=(0, 1))


def tokens_from_keys(keys: np.ndarray, vocab: int) -> np.ndarray:
    """Map raw event keys (any uint width) onto the LM token space."""
    return (keys.astype(np.uint64) % np.uint64(vocab)).astype(np.int32)
