"""Distributed sketch pipeline tests on the 8-device virtual CPU mesh.

Validates the cluster-merge contract: per-node sharded sketch updates +
collective merge must equal the sequential union (the correctness bar the
reference meets with client-side merging, pkg/snapshotcombiner tests).
"""

import numpy as np
import jax
import jax.numpy as jnp

from inspektor_gadget_tpu.models import AEConfig, ae_init, ae_score, ae_train_step
from inspektor_gadget_tpu.models.autoencoder import normalize_counts
from inspektor_gadget_tpu.ops import bundle_init, bundle_update, cms_query, hll_estimate
from inspektor_gadget_tpu.parallel import (
    cluster_init,
    make_cluster_step,
    make_mesh,
)

BATCH = 256
DIM = 256


def small_cfg():
    return AEConfig(input_dim=DIM, hidden_dim=128, latent_dim=32)


def small_bundle_kw():
    return dict(depth=4, log2_width=12, hll_p=10, entropy_log2_width=8, k=32)


def test_mesh_axes():
    mesh = make_mesh()
    assert mesh.shape["node"] == 8
    mesh2 = make_mesh(n_nodes=4, n_model=2)
    assert mesh2.shape == {"node": 4, "model": 2}


def test_autoencoder_trains_and_scores():
    cfg = small_cfg()
    scorer = ae_init(cfg)
    rng = np.random.default_rng(0)
    x = normalize_counts(jnp.asarray(rng.poisson(5.0, (64, DIM)).astype(np.float32)))
    losses = []
    for _ in range(30):
        scorer, loss = ae_train_step(scorer, x)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.5  # learns the distribution
    normal_score = float(ae_score(scorer, x).mean())
    weird = jnp.zeros((4, DIM), jnp.float32).at[:, 3].set(1.0)
    anomaly_score = float(ae_score(scorer, weird).mean())
    assert anomaly_score > normal_score


def test_cluster_step_matches_sequential_union():
    mesh = make_mesh(n_nodes=8)
    scorer = ae_init(small_cfg())
    state = cluster_init(mesh, scorer, **small_bundle_kw())
    step, merge = make_cluster_step(mesh, state)

    rng = np.random.default_rng(1)
    keys = rng.zipf(1.3, (8, BATCH)).clip(1, 10_000).astype(np.uint32)
    mask = np.ones((8, BATCH), dtype=bool)
    ae_batch = rng.poisson(3.0, (8, 16, DIM)).astype(np.float32)

    state, loss = step(
        state, jnp.asarray(keys), jnp.asarray(keys), jnp.asarray(keys),
        jnp.asarray(mask), jnp.asarray(ae_batch),
    )
    assert np.isfinite(float(loss))
    merged = merge(state.bundle)

    # sequential reference: all 8 node batches through one bundle
    seq = bundle_init(**small_bundle_kw())
    for i in range(8):
        seq = bundle_update(
            seq, jnp.asarray(keys[i]), jnp.asarray(keys[i]), jnp.asarray(keys[i]),
            jnp.ones(BATCH, bool),
        )

    assert float(merged.events) == 8 * BATCH
    assert jnp.array_equal(merged.cms.table, seq.cms.table)
    assert jnp.array_equal(merged.hll.registers, seq.hll.registers)
    np.testing.assert_allclose(
        np.asarray(merged.entropy.counts), np.asarray(seq.entropy.counts), rtol=1e-6
    )
    # merged top-k should surface the global heavy hitter
    uniq, counts = np.unique(keys, return_counts=True)
    true_top = uniq[np.argmax(counts)]
    tk = np.asarray(merged.topk.keys)
    assert true_top in tk


def test_cluster_distinct_counting_across_nodes():
    mesh = make_mesh(n_nodes=8)
    scorer = ae_init(small_cfg())
    state = cluster_init(mesh, scorer, **small_bundle_kw())
    step, merge = make_cluster_step(mesh, state)
    # each node sees a disjoint key range; merged HLL must see the union
    keys = np.arange(8 * BATCH, dtype=np.uint32).reshape(8, BATCH) * np.uint32(2654435761)
    mask = np.ones((8, BATCH), dtype=bool)
    ae_batch = np.ones((8, 8, DIM), dtype=np.float32)
    state, _ = step(state, jnp.asarray(keys), jnp.asarray(keys), jnp.asarray(keys),
                    jnp.asarray(mask), jnp.asarray(ae_batch))
    merged = merge(state.bundle)
    est = float(hll_estimate(merged.hll))
    assert abs(est - 8 * BATCH) / (8 * BATCH) < 0.1


def test_scorer_stays_replicated_and_synced():
    mesh = make_mesh(n_nodes=8)
    scorer = ae_init(small_cfg())
    state = cluster_init(mesh, scorer, **small_bundle_kw())
    step, _ = make_cluster_step(mesh, state)
    rng = np.random.default_rng(2)
    keys = np.ones((8, BATCH), dtype=np.uint32)
    mask = np.ones((8, BATCH), dtype=bool)
    # different data per node — pmean grads must keep replicas identical
    ae_batch = rng.poisson(3.0, (8, 8, DIM)).astype(np.float32)
    state, _ = step(state, jnp.asarray(keys), jnp.asarray(keys), jnp.asarray(keys),
                    jnp.asarray(mask), jnp.asarray(ae_batch))
    w = state.scorer.params["enc1"]["w"]
    shards = [np.asarray(s.data) for s in w.addressable_shards]
    for s in shards[1:]:
        np.testing.assert_array_equal(shards[0], s)


def test_tp_autoencoder_matches_replicated():
    """TP forward (Megatron sharding, psum contractions) must equal the
    single-device forward on the same weights."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from inspektor_gadget_tpu.models.autoencoder import (
        AEConfig, ae_init, ae_apply, ae_apply_tp)
    from inspektor_gadget_tpu.parallel.cluster import scorer_pspecs
    from inspektor_gadget_tpu.parallel import make_mesh

    cfg = AEConfig(input_dim=128, hidden_dim=128, latent_dim=32,
                   compute_dtype=jnp.float32)
    scorer = ae_init(cfg, seed=3)
    x = normalize_counts(jnp.asarray(
        np.random.default_rng(0).poisson(4.0, (8, 128)).astype(np.float32)))
    ref = ae_apply(scorer.params, x, cfg)

    mesh = make_mesh(n_nodes=4, n_model=2)
    specs = scorer_pspecs(scorer)
    from jax import shard_map
    tp_fn = jax.jit(shard_map(
        lambda p, xx: ae_apply_tp(p, xx, cfg, model_axis="model"),
        mesh=mesh,
        in_specs=(specs.params, P()),
        out_specs=P(),
        check_vma=False,
    ))
    sharded_params = jax.device_put(
        scorer.params,
        jax.tree.map(lambda s: NamedSharding(mesh, s), specs.params,
                     is_leaf=lambda v: isinstance(v, P)))
    out = tp_fn(sharded_params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_cluster_step_tp_mode():
    mesh = make_mesh(n_nodes=4, n_model=2)
    scorer = ae_init(AEConfig(input_dim=DIM, hidden_dim=128, latent_dim=32))
    state = cluster_init(mesh, scorer, **small_bundle_kw())
    step, merge = make_cluster_step(mesh, state)
    rng = np.random.default_rng(9)
    keys = rng.integers(1, 2**32, (4, BATCH), dtype=np.uint32)
    mask = np.ones((4, BATCH), bool)
    ae_batch = rng.poisson(3.0, (4, 8, DIM)).astype(np.float32)
    state, loss = step(state, jnp.asarray(keys), jnp.asarray(keys),
                       jnp.asarray(keys), jnp.asarray(mask),
                       jnp.asarray(ae_batch))
    assert np.isfinite(float(loss))
    merged = merge(state.bundle)
    assert float(merged.events) == 4 * BATCH


def test_ring_psum_variants_match_allreduce():
    """Ring all-reduce (ppermute hops) and the reduce-scatter/all-gather
    ring must equal lax.psum exactly on integer tables."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from inspektor_gadget_tpu.parallel.ring import ring_psum, ring_psum_chunked

    mesh = make_mesh(n_nodes=8)
    x = jnp.arange(8 * 37, dtype=jnp.int32).reshape(8, 37)
    want = np.broadcast_to(np.asarray(x).sum(0), (8, 37))
    for fn in (ring_psum, ring_psum_chunked):
        f = jax.jit(shard_map(
            lambda v: fn(v[0], "node")[None], mesh=mesh,
            in_specs=(P("node"),), out_specs=P("node"), check_vma=False))
        np.testing.assert_array_equal(np.asarray(f(x)), want)


def test_vae_trains_and_scores_anomalies():
    from inspektor_gadget_tpu.models import VAEConfig, vae_init, vae_score, vae_train_step

    cfg = VAEConfig(input_dim=DIM, hidden_dim=128, latent_dim=16,
                    compute_dtype=jnp.float32)
    scorer = vae_init(cfg, seed=1)
    rng = np.random.default_rng(0)
    x = normalize_counts(jnp.asarray(rng.poisson(5.0, (64, DIM)).astype(np.float32)))
    losses = []
    for _ in range(30):
        scorer, loss = vae_train_step(scorer, x)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    normal = float(vae_score(scorer, x).mean())
    weird = jnp.zeros((4, DIM), jnp.float32).at[:, 5].set(1.0)
    assert float(vae_score(scorer, weird).mean()) > normal


def test_axis_size_is_static_under_shard_map():
    """The ring schedules build their permutation lists from
    `lax.axis_size`, so it must be a static Python int inside a
    `jax.shard_map` body on the installed jax."""
    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh(n_nodes=4, n_model=1)

    def body(x):
        n = lax.axis_size("node")
        assert isinstance(n, int) and n == 4
        return (x[0] * 2)[None]

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("node"),),
                          out_specs=P("node"), check_vma=False))
    x = jnp.arange(8, dtype=jnp.int32).reshape(4, 2)
    np.testing.assert_array_equal(np.asarray(f(x)), np.asarray(x) * 2)


def test_ingest_mesh_shape_and_validation():
    """ingest_mesh (ISSUE 14): a (node)-only mesh over the first N local
    devices, loud on impossible chip counts."""
    import pytest

    from inspektor_gadget_tpu.parallel.mesh import ingest_mesh

    mesh = ingest_mesh(4)
    assert mesh.shape == {"node": 4}
    assert ingest_mesh(1).shape == {"node": 1}
    with pytest.raises(ValueError, match="exceeds"):
        ingest_mesh(99)
    with pytest.raises(ValueError, match=">= 1"):
        ingest_mesh(0)
