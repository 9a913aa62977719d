"""DCN collective merge: the aggregation tier for chip-bearing hosts.

When the "fleet" is one multihost TPU slice (or several), the merge
tree does not need gRPC hops at all — the PR-11 sharded harvest already
leaves one fused SketchBundle per chip, and `cluster_merge` is a single
collective over the node axis. `make_multihost_mesh` orders devices
slice-major (slice_index, process_index, id), so the psum/pmax tree
rides ICI within each slice and crosses DCN once per slice pair — the
fleet-merged bundle materializes ON DEVICE and the invertible decode
runs on the *merged* state (arxiv 1910.10441's network-wide recovery,
arxiv 2503.13515's disaggregation across space).

Bit-identity contract: every lane the collective folds is integer
arithmetic — CMS/entropy/DDSketch/invertible counts psum (int lanes;
the mod-2^32 key-sum/fingerprint lanes wrap identically under any
association), HLL registers pmax, top-k all_gather in mesh order — so
the CPU-simulated multi-process merge is bit-identical to the same
merge on one process, and to the host-side flat fold of the equivalent
sealed windows. tests/test_fleet_collective.py pins the first two;
TPU verification of the DCN crossing rides the standing hardware-probe
item (a degraded/cpu run may not read as a TPU result).
"""

from __future__ import annotations

import jax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.sketches import SketchBundle
from ..parallel.cluster import cluster_merge
from ..parallel.mesh import NODE_AXIS


def fleet_collective_merge(bundle: SketchBundle) -> SketchBundle:
    """The shard_map body: per-node bundles (leading node-axis dim) →
    ONE replicated fleet bundle. Exactly `cluster_merge` — the tier
    reuses the PR-11 harvest algebra verbatim so the on-device fold and
    the host-side window fold cannot drift apart."""
    return cluster_merge(bundle)


def make_fleet_merge(mesh: Mesh):
    """Jitted collective merge over `mesh`'s node axis.

    merge(stacked_bundle) -> replicated fleet SketchBundle, where
    `stacked_bundle` has a leading node-axis dim sharded over the mesh
    (one bundle row per chip/host lane). On a `make_multihost_mesh`
    mesh the reduction crosses DCN once per slice; on a single-host
    mesh it is the PR-11 harvest unchanged."""

    def specs_like(tree, spec):
        return jax.tree.map(lambda _: spec, tree)

    def merge_fn(stacked: SketchBundle) -> SketchBundle:
        in_specs = (specs_like(stacked, P(NODE_AXIS)),)
        out_specs = specs_like(
            jax.tree.map(lambda x: x[0], stacked), P())
        return jax.jit(shard_map(
            fleet_collective_merge, mesh=mesh, in_specs=in_specs,
            out_specs=out_specs, check_vma=False))(stacked)

    return merge_fn


def shard_over_nodes(mesh: Mesh, stacked: SketchBundle) -> SketchBundle:
    """Place a host-stacked bundle (leading dim = node count) onto the
    mesh's node axis — the single-process analogue of each host calling
    `jax.make_array_from_process_local_data` on its own rows."""
    sharding = NamedSharding(mesh, P(NODE_AXIS))
    return jax.tree.map(lambda x: jax.device_put(x, sharding), stacked)


def bundle_digest(bundle: SketchBundle) -> str:
    """sha256 over every plane's raw bytes in field order — the
    bit-identity witness two processes (or two fold shapes) compare.
    Optional planes hash their presence flag so plane-off and plane-on
    bundles can never collide."""
    import hashlib

    import numpy as np

    h = hashlib.sha256()
    for leaf in jax.tree.leaves(bundle):
        arr = np.asarray(leaf)
        h.update(str(arr.dtype).encode())
        h.update(str(arr.shape).encode())
        h.update(arr.tobytes())
    h.update(str(jax.tree.structure(bundle)).encode())
    return h.hexdigest()


__all__ = ["bundle_digest", "fleet_collective_merge", "make_fleet_merge",
           "shard_over_nodes"]
