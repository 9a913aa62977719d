"""Pallas flash-attention tests (interpret mode on CPU — same kernel code
path as the compiled TPU run, which was validated on hardware; see
docs/performance.md)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from inspektor_gadget_tpu.parallel import flash_attention
from inspektor_gadget_tpu.parallel.ring_attention import full_attention


@pytest.mark.parametrize("shape,causal", [
    ((2, 256, 4, 32), True),     # D padding (32 → 128 lanes)
    ((1, 200, 2, 16), False),    # T padding (200 → 256) + D padding
    ((2, 128, 1, 128), True),    # exact hardware shapes, single block
    ((1, 384, 2, 64), True),     # multi-block causal early exit
])
def test_flash_matches_reference(shape, causal):
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=shape).astype(np.float32))
               for _ in range(3))
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_first_row_attends_only_self():
    """Causal row 0 must equal v[0] exactly (softmax over one key)."""
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 128, 1, 32)).astype(np.float32))
               for _ in range(3))
    out = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out[0, 0, 0]),
                               np.asarray(v[0, 0, 0]), rtol=1e-5, atol=1e-5)


def test_flash_gradients_match_reference():
    """custom_vjp: grads through the flash kernel equal grads through full
    attention (backward recomputes via the blockwise path)."""
    rng = np.random.default_rng(2)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 128, 2, 32)).astype(np.float32))
               for _ in range(3))

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True, interpret=True) ** 2).sum()

    def loss_full(q, k, v):
        return (full_attention(q, k, v, causal=True) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


def test_flash_gradients_odd_length():
    """Backward path handles T with no small divisors (prime T=251) via
    q-block padding — no degenerate chunk=1 scan."""
    rng = np.random.default_rng(4)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 251, 2, 16)).astype(np.float32))
               for _ in range(3))

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True, interpret=True) ** 2).sum()

    def loss_full(q, k, v):
        return (full_attention(q, k, v, causal=True) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


@pytest.fixture
def interpreted_flash(monkeypatch):
    """seqmodel's attn='flash' backend calls the kernel with the library
    default (compile for the TPU); on the CPU the test steers it into the
    interpreter explicitly."""
    import functools

    from inspektor_gadget_tpu.models import seqmodel
    monkeypatch.setattr(seqmodel, "flash_attention", functools.partial(
        flash_attention, interpret=True))


def test_flash_training_end_to_end(interpreted_flash):
    """seq_train_step(attn='flash') learns: fused forward + recompute
    backward through the whole model."""
    from inspektor_gadget_tpu.models.seqmodel import (
        SeqConfig, seq_init, seq_train_step,
    )

    cfg = SeqConfig(vocab=16, d_model=16, n_heads=2, n_layers=1, d_ff=32)
    sc = seq_init(cfg)
    rng = np.random.default_rng(3)
    toks = jnp.asarray(np.tile(rng.integers(0, 16, (2, 2)), (1, 64)),
                       jnp.int32)
    losses = []
    for _ in range(15):
        sc, loss = seq_train_step(sc, toks, attn="flash")
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.8


def test_seqmodel_flash_backend(interpreted_flash):
    """attn='flash' scores through the kernel and matches the full-attention
    backend (the per-container NLL hot loop)."""
    from inspektor_gadget_tpu.models.seqmodel import (
        SeqConfig, seq_init, seq_score,
    )

    cfg = SeqConfig(vocab=32, d_model=32, n_heads=2, n_layers=1, d_ff=64)
    sc = seq_init(cfg, seed=0)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, 32, (2, 128)), jnp.int32)
    s_flash = seq_score(sc, toks, attn="flash")
    s_full = seq_score(sc, toks, attn="full")
    np.testing.assert_allclose(np.asarray(s_flash), np.asarray(s_full),
                               rtol=1e-3, atol=1e-3)
