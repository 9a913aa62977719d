"""DDSketch quantile-plane tests: relative-error guarantee, mergeability
(sharded == sequential, the cluster-merge contract), log2 re-binning parity
with the reference's biolatency histogram."""

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from inspektor_gadget_tpu.ops import (
    dd_histogram_log2, dd_init, dd_merge, dd_psum, dd_quantile, dd_update,
)


def test_quantile_relative_error_bound():
    rng = np.random.default_rng(0)
    vals = rng.lognormal(mean=-6.0, sigma=2.0, size=20000).astype(np.float32)
    sk = dd_init(alpha=0.01)
    sk = jax.jit(dd_update)(sk, jnp.asarray(vals))
    for q in (0.5, 0.9, 0.95, 0.99):
        est = float(dd_quantile(sk, q))
        true = float(np.quantile(vals, q))
        assert abs(est - true) / true < 0.02, (q, est, true)


def test_zero_bucket_and_empty():
    sk = dd_init(alpha=0.02)
    assert np.isnan(float(dd_quantile(sk, 0.5)))
    vals = jnp.asarray([0.0, 0.0, 0.0, 1.0], jnp.float32)
    sk = dd_update(sk, vals)
    assert float(sk.zeros) == 3.0
    assert float(dd_quantile(sk, 0.25)) == 0.0   # rank inside zero bucket
    est = float(dd_quantile(sk, 1.0))
    assert abs(est - 1.0) < 0.05


def test_mask_and_merge_equals_sequential():
    rng = np.random.default_rng(1)
    a = rng.exponential(0.01, 4096).astype(np.float32)
    b = rng.exponential(0.10, 4096).astype(np.float32)
    mask = np.ones(4096, bool)
    mask[2048:] = False  # padding slots must not count
    sk_a = dd_update(dd_init(), jnp.asarray(a), jnp.asarray(mask))
    sk_b = dd_update(dd_init(), jnp.asarray(b), jnp.asarray(mask))
    merged = dd_merge(sk_a, sk_b)
    seq = dd_update(dd_update(dd_init(), jnp.asarray(a), jnp.asarray(mask)),
                    jnp.asarray(b), jnp.asarray(mask))
    np.testing.assert_array_equal(np.asarray(merged.counts),
                                  np.asarray(seq.counts))
    assert float(merged.total) == float(mask.sum()) * 2
    both = np.concatenate([a[:2048], b[:2048]])
    est = float(dd_quantile(merged, 0.5))
    true = float(np.quantile(both, 0.5))
    assert abs(est - true) / true < 0.02


def test_cluster_psum_merge_over_mesh():
    """Per-node latency shards psum-merged == global sketch (the
    snapshotcombiner role for quantiles)."""
    rng = np.random.default_rng(2)
    vals = rng.lognormal(-5.0, 1.0, (8, 2048)).astype(np.float32)
    mesh = Mesh(np.array(jax.devices()[:8]), ("node",))

    def update_and_merge(v):
        sk = dd_update(dd_init(), v)
        return dd_psum(sk, "node")

    from jax import shard_map
    merged = jax.jit(shard_map(
        update_and_merge, mesh=mesh, in_specs=P("node"),
        out_specs=P(), check_vma=False))(jnp.asarray(vals))
    est = float(dd_quantile(merged, 0.95))
    true = float(np.quantile(vals.reshape(-1), 0.95))
    assert float(merged.total) == vals.size
    assert abs(est - true) / true < 0.02


def test_log2_rebinning_conserves_counts():
    rng = np.random.default_rng(3)
    vals = rng.lognormal(-7.0, 1.5, 8192).astype(np.float32)
    sk = dd_update(dd_init(), jnp.asarray(vals))
    hist = dd_histogram_log2(sk)
    assert float(hist.sum()) == float(sk.counts.sum())
    # mass concentrates around log2(us) of the distribution median
    med_us = np.quantile(vals, 0.5) * 1e6
    peak_slot = int(np.argmax(np.asarray(hist)))
    assert abs(peak_slot - np.log2(med_us)) <= 2.5


def test_int32_counts_exact_past_f32_mantissa():
    """The count lanes are int32 on purpose: an f32 tally silently stops
    incrementing at 2^24 (x + 1 == x). Seed a bucket at exactly 2^24 and
    fold one more value into it — the increment must land."""
    sk = dd_init(alpha=0.01, min_value=1.0)
    seed = 1 << 24
    sk = sk.replace(counts=sk.counts.at[100].set(seed),
                    total=jnp.asarray(seed, jnp.int32))
    # bucket-100 midpoint: ceil(log_gamma(mid)) == 100
    mid = 2.0 * sk.gamma ** 100 / (sk.gamma + 1.0)
    sk = jax.jit(dd_update)(sk, jnp.asarray([mid], jnp.float32))
    assert int(sk.counts[100]) == seed + 1
    assert int(sk.total) == seed + 1


def test_quantile_monotone_in_q():
    rng = np.random.default_rng(4)
    vals = rng.lognormal(-5.0, 2.5, 10000).astype(np.float32)
    sk = dd_update(dd_init(alpha=0.02), jnp.asarray(vals))
    qs = [0.0, 0.01, 0.1, 0.25, 0.5, 0.75, 0.9, 0.99, 0.999, 1.0]
    reads = [float(dd_quantile(sk, q)) for q in qs]
    assert all(a <= b for a, b in zip(reads, reads[1:])), reads


def test_merge_order_invariance():
    """Bucket-wise int adds are associative AND commutative, so any fold
    order over node shards yields bit-identical lanes — the property the
    sealed-window pushdown/client-side fold split relies on."""
    rng = np.random.default_rng(5)
    chunks = [rng.exponential(10.0 ** -i, 1024).astype(np.float32)
              for i in range(4)]
    sketches = [dd_update(dd_init(), jnp.asarray(c)) for c in chunks]
    fwd = sketches[0]
    for s in sketches[1:]:
        fwd = dd_merge(fwd, s)
    rev = sketches[3]
    for s in (sketches[1], sketches[2], sketches[0]):
        rev = dd_merge(rev, s)
    np.testing.assert_array_equal(np.asarray(fwd.counts),
                                  np.asarray(rev.counts))
    assert int(fwd.zeros) == int(rev.zeros)
    assert int(fwd.total) == int(rev.total)


def test_psum_equals_pairwise_merge():
    """dd_psum over a mesh axis must be bit-identical to folding the
    per-shard sketches with dd_merge on the host."""
    rng = np.random.default_rng(6)
    vals = rng.lognormal(-6.0, 1.5, (8, 512)).astype(np.float32)
    mesh = Mesh(np.array(jax.devices()[:8]), ("node",))
    from jax import shard_map
    merged = jax.jit(shard_map(
        lambda v: dd_psum(dd_update(dd_init(), v), "node"),
        mesh=mesh, in_specs=P("node"), out_specs=P(),
        check_vma=False))(jnp.asarray(vals))
    pair = dd_update(dd_init(), jnp.asarray(vals[0]))
    for row in vals[1:]:
        pair = dd_merge(pair, dd_update(dd_init(), jnp.asarray(row)))
    np.testing.assert_array_equal(np.asarray(merged.counts),
                                  np.asarray(pair.counts))
    assert int(merged.zeros) == int(pair.zeros)
    assert int(merged.total) == int(pair.total)


def test_host_twins_match_device_reads():
    """dd_quantile_np / dd_histogram_log2_np (the sealed-window fold path)
    agree with the device reads over the same raw lanes."""
    from inspektor_gadget_tpu.ops.quantiles import (
        dd_histogram_log2_np, dd_quantile_np,
    )
    rng = np.random.default_rng(7)
    vals = rng.lognormal(-5.5, 1.8, 8192).astype(np.float32)
    vals[:100] = 0.0  # exercise the zero bucket
    sk = dd_update(dd_init(), jnp.asarray(vals))
    counts = np.asarray(sk.counts)
    zeros, total = int(sk.zeros), int(sk.total)
    for q in (0.005, 0.5, 0.9, 0.99):
        dev = float(dd_quantile(sk, q))
        host = float(dd_quantile_np(counts, zeros, total, q,
                                    alpha=sk.alpha, min_value=sk.min_value))
        assert np.isclose(dev, host, rtol=1e-5), (q, dev, host)
    # array-q form matches the scalar reads
    arr = dd_quantile_np(counts, zeros, total, np.asarray([0.5, 0.99]),
                         alpha=sk.alpha, min_value=sk.min_value)
    assert arr.shape == (2,)
    # empty sketch: NaN on both twins
    assert np.isnan(float(dd_quantile_np(np.zeros(16), 0, 0, 0.5)))
    dev_hist = np.asarray(dd_histogram_log2(sk))
    host_hist = dd_histogram_log2_np(counts, alpha=sk.alpha,
                                     min_value=sk.min_value)
    np.testing.assert_array_equal(dev_hist, host_hist)
