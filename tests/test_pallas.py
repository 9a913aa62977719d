"""Pallas kernel tests (CPU: the XLA reference path and the kernels'
helpers; the kernels themselves compile for a described v5e in
tests/test_tpu_compile.py and run on the chip in chip_smoke.py)."""

import numpy as np
import jax.numpy as jnp

from inspektor_gadget_tpu.ops.pallas_kernels import _u32_to_f32, xla_histogram
from inspektor_gadget_tpu.ops.entropy import entropy_init, entropy_update
from inspektor_gadget_tpu.ops.hashing import multiply_shift


def test_xla_histogram_matches_manual():
    rng = np.random.default_rng(0)
    keys = jnp.asarray(rng.integers(0, 2**32, 4096, dtype=np.uint32))
    w = jnp.ones(4096, jnp.float32)
    h = xla_histogram(keys, w, log2_width=10)
    assert float(h.sum()) == 4096
    # same hash family as the sketch plane's row 0
    idx = multiply_shift(keys, 0, 10)
    manual = np.zeros(1024, np.float32)
    np.add.at(manual, np.asarray(idx), 1.0)
    np.testing.assert_array_equal(np.asarray(h), manual)


def test_entropy_update_consistent_across_backends():
    # on CPU this takes the scatter path; sums and estimates must agree
    keys = jnp.arange(512, dtype=jnp.uint32)
    e = entropy_update(entropy_init(10), keys)
    assert float(e.counts.sum()) == 512


def test_u32_to_f32_equals_the_direct_convert():
    """The TPU compiler has no uint32 -> float32 convert, so the quantile
    plane's value lane goes through int32 halves with a sticky bit; the
    result must be the direct convert's, bit for bit (round to nearest
    even, ties included), or kernel and reference would bin differently."""
    rng = np.random.default_rng(3)
    edge = np.array([0, 1, (1 << 24) - 1, 1 << 24, (1 << 24) + 1,
                     (1 << 31) - 1, 1 << 31, (1 << 31) + 1,
                     (1 << 31) + 128, (1 << 31) + 129, (1 << 31) + 384,
                     (1 << 32) - 257, (1 << 32) - 129, (1 << 32) - 128,
                     (1 << 32) - 1], dtype=np.uint64).astype(np.uint32)
    vals = np.concatenate(
        [edge, rng.integers(0, 1 << 32, 1 << 16, dtype=np.uint32)])
    got = np.asarray(_u32_to_f32(jnp.asarray(vals)))
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got, vals.astype(np.float32))
