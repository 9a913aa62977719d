"""Mesh construction and sharding specs.

Axes:
  node   data-parallel over event streams (one shard per node/chip group) —
         sketch updates are per-node, merges are collectives over this axis.
  model  tensor-parallel axis for the autoencoder matmuls (used when the
         slice has more chips than event streams).

Within one pod slice both axes ride ICI; across slices the node axis maps
onto DCN — mirroring the reference's node-local (unix socket) vs cluster
(kubectl-exec gRPC) split (pkg/gadgettracermanager main.go:66-67 vs
pkg/runtime/grpc/k8s-exec-dialer.go).
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NODE_AXIS = "node"
MODEL_AXIS = "model"


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    n_nodes: int
    n_model: int = 1


def node_axis() -> str:
    return NODE_AXIS


def make_mesh(n_nodes: int | None = None, n_model: int = 1,
              devices=None) -> Mesh:
    """Build a (node, model) mesh. Defaults: all local devices on the node
    axis. On a real multi-host slice, pass jax.devices() after
    jax.distributed.initialize()."""
    if devices is None:
        devices = jax.devices()
    if n_nodes is None:
        n_nodes = len(devices) // n_model
    devs = np.asarray(devices[: n_nodes * n_model]).reshape(n_nodes, n_model)
    return Mesh(devs, (NODE_AXIS, MODEL_AXIS))


def ingest_mesh(chips: int, devices=None) -> Mesh:
    """The (node)-only mesh the sharded ingest plane runs on (ISSUE 14):
    `chips` local devices, one SketchBundle replica each, collectives only
    at harvest. A 1-chip mesh is legal; the operator short-circuits
    chips=1 to the unsharded path."""
    if devices is None:
        devices = jax.local_devices()
    if chips < 1:
        raise ValueError(f"chips must be >= 1, got {chips}")
    if chips > len(devices):
        raise ValueError(
            f"chips={chips} exceeds the {len(devices)} local device(s)")
    return Mesh(np.asarray(devices[:chips]), (NODE_AXIS,))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Event batches shard over the node axis (leading dim = node)."""
    return NamedSharding(mesh, P(NODE_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
