"""Cluster-wide sketch pipeline: per-node updates + collective merges.

This is the distributed heart of the framework — the TPU equivalent of the
reference's fan-out/merge runtime (pkg/runtime/grpc/grpc-runtime.go:185-239
merging one JSON stream per node; pkg/snapshotcombiner's TTL ticker merge).

Design: each mesh 'node' shard holds its own SketchBundle (sketch arrays are
*sharded* over the node axis — state lives where events land). One jitted
`cluster_step` under shard_map:
  1. absorbs that node's event batch into its local bundle,
  2. trains the shared autoencoder data-parallel (pmean grads),
  3. computes the *merged* cluster view (psum CMS/entropy, pmax HLL,
     all_gather+rerank top-k) — returned as a replicated summary without
     ever moving raw events off-node.

The merged view is recomputed per harvest tick, not per batch — matching the
reference's interval semantics (snapshotcombiner ticker) while keeping the
hot path collective-free.
"""

from __future__ import annotations

import flax.struct
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.autoencoder import (
    AnomalyScorer,
    ae_param_pspecs,
    ae_train_step,
    ae_train_step_tp,
    normalize_counts,
)
from ..ops.countmin import cms_psum
from ..ops.entropy import entropy_psum
from ..ops.hll import hll_pmax
from ..ops.invertible import inv_psum
from ..ops.quantiles import dd_psum
from ..ops.sketches import SketchBundle, bundle_init, bundle_update
from ..ops.topk import topk_gather_merge
from .mesh import MODEL_AXIS, NODE_AXIS


@flax.struct.dataclass
class ClusterState:
    """Per-node bundles (sharded over 'node') + replicated scorer."""

    bundle: SketchBundle
    scorer: AnomalyScorer


def scorer_pspecs(scorer: AnomalyScorer, model_axis: str = MODEL_AXIS):
    """PartitionSpec tree for the scorer: Megatron row/col sharding on the
    params and matching sharding on Adam's mu/nu (same inner structure)."""
    pp = ae_param_pspecs(model_axis)

    def for_path(path, _leaf):
        keys = [k.key for k in path
                if isinstance(k, jax.tree_util.DictKey)]
        for layer in ("enc1", "enc2", "dec1", "dec2"):
            if layer in keys:
                return pp[layer]["w" if "w" in keys else "b"]
        return P()

    return AnomalyScorer(
        params=jax.tree_util.tree_map_with_path(for_path, scorer.params),
        opt_state=jax.tree_util.tree_map_with_path(for_path, scorer.opt_state),
        steps=P(),
        config=scorer.config,
    )


def cluster_init(mesh: Mesh, scorer: AnomalyScorer, **bundle_kw) -> ClusterState:
    """Materialize state with the right shardings: bundle arrays get a
    leading node-axis dim (one bundle per node); the scorer replicates on a
    1-D mesh and tensor-shards over the 'model' axis on a 2-D mesh."""
    n = mesh.shape[NODE_AXIS]
    tp = mesh.shape.get(MODEL_AXIS, 1) > 1

    def stack(x):
        return jax.device_put(
            jnp.broadcast_to(x, (n,) + x.shape),
            NamedSharding(mesh, P(NODE_AXIS)),
        )

    bundle = jax.tree.map(stack, bundle_init(**bundle_kw))
    if tp:
        specs = scorer_pspecs(scorer)
        shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))
        scorer = jax.device_put(scorer, shardings)
    else:
        scorer = jax.device_put(scorer, NamedSharding(mesh, P()))
    return ClusterState(bundle=bundle, scorer=scorer)


def cluster_sketch_step(
    state: ClusterState,
    hh_keys: jnp.ndarray,      # (n_nodes, batch) uint32
    distinct_keys: jnp.ndarray,
    dist_keys: jnp.ndarray,
    mask: jnp.ndarray,         # (n_nodes, batch) bool
    ae_batch: jnp.ndarray,     # (n_nodes, rows, input_dim) float32 counts
    use_tp: bool = False,
) -> tuple[ClusterState, jnp.ndarray]:
    """Per-node shard body (runs under shard_map; leading node dim is 1)."""
    bundle = jax.tree.map(lambda x: x[0], state.bundle)
    bundle = bundle_update(bundle, hh_keys[0], distinct_keys[0], dist_keys[0], mask[0])
    x = normalize_counts(ae_batch[0])
    if use_tp:
        scorer, loss = ae_train_step_tp(
            state.scorer, x, dp_axis=NODE_AXIS, model_axis=MODEL_AXIS)
    else:
        scorer, loss = ae_train_step(state.scorer, x, axis_name=NODE_AXIS)
    bundle = jax.tree.map(lambda x: x[None], bundle)
    return ClusterState(bundle=bundle, scorer=scorer), loss


def cluster_merge(bundle: SketchBundle) -> SketchBundle:
    """Collective merge of per-node bundles into the cluster view (runs
    under shard_map over the node axis). CMS/entropy psum, HLL pmax, top-k
    all_gather + re-rank vs the merged CMS, invertible lanes psum (the
    whole point of the invertible plane: decode runs on THIS state),
    DDSketch quantile row psum (cluster-wide latency distribution)."""
    local = jax.tree.map(lambda x: x[0], bundle)
    cms = cms_psum(local.cms, NODE_AXIS)
    merged = SketchBundle(
        cms=cms,
        hll=hll_pmax(local.hll, NODE_AXIS),
        entropy=entropy_psum(local.entropy, NODE_AXIS),
        topk=topk_gather_merge(local.topk, cms, NODE_AXIS),
        events=jax.lax.psum(local.events, NODE_AXIS),
        drops=jax.lax.psum(local.drops, NODE_AXIS),
        inv=(inv_psum(local.inv, NODE_AXIS)
             if local.inv is not None else None),
        quantiles=(dd_psum(local.quantiles, NODE_AXIS)
                   if local.quantiles is not None else None),
    )
    return merged


def _specs_like(tree, spec):
    """PartitionSpec pytree with `spec` at every array leaf of `tree`."""
    return jax.tree.map(lambda _: spec, tree)


def make_cluster_step(mesh: Mesh, state: ClusterState):
    """Jitted SPMD pair: (step, merge).

    step(state, hh, distinct, dist, mask, ae_batch) -> (state, loss)
      per-node sketch update + DP autoencoder train; no cross-node
      collectives except the grad pmean.
    merge(bundle_sharded) -> replicated cluster SketchBundle
      the harvest-tick collective (snapshotcombiner analogue).
    """
    use_tp = mesh.shape.get(MODEL_AXIS, 1) > 1
    state_specs = ClusterState(
        bundle=_specs_like(state.bundle, P(NODE_AXIS)),
        scorer=(scorer_pspecs(state.scorer) if use_tp
                else _specs_like(state.scorer, P())),
    )
    batch_spec = P(NODE_AXIS)

    import functools
    step = jax.jit(
        shard_map(
            functools.partial(cluster_sketch_step, use_tp=use_tp),
            mesh=mesh,
            in_specs=(state_specs, batch_spec, batch_spec, batch_spec,
                      batch_spec, batch_spec),
            out_specs=(state_specs, P()),
            check_vma=False,
        ),
        donate_argnums=0,
    )

    merge = jax.jit(
        shard_map(
            cluster_merge,
            mesh=mesh,
            in_specs=(_specs_like(state.bundle, P(NODE_AXIS)),),
            out_specs=_specs_like(jax.tree.map(lambda x: x[0], state.bundle), P()),
            check_vma=False,
        )
    )
    return step, merge
