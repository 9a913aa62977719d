"""DCN collective merge tier (ISSUE 20): `make_fleet_merge` must be
the PR-11 cluster harvest unchanged — bit-identical to `cluster_merge`
on one process, deterministic across placements, and (when the backend
supports cross-process CPU collectives) bit-identical between the two
halves of a simulated two-host world."""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map

from inspektor_gadget_tpu.fleet.collective import (
    bundle_digest,
    fleet_collective_merge,
    make_fleet_merge,
    shard_over_nodes,
)
from inspektor_gadget_tpu.ops import bundle_init, bundle_update
from inspektor_gadget_tpu.parallel import make_mesh
from inspektor_gadget_tpu.parallel.mesh import NODE_AXIS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N_NODES = 8
BATCH = 256
BUNDLE_KW = dict(depth=4, log2_width=10, hll_p=8, entropy_log2_width=7,
                 k=16)


def per_node_bundles(seed: int = 0):
    """One updated bundle per node, stacked on a leading node axis —
    what the sharded harvest leaves per chip."""
    rng = np.random.default_rng(seed)
    keys = rng.zipf(1.3, (N_NODES, BATCH)).clip(1, 10_000).astype(
        np.uint32)
    rows = []
    for i in range(N_NODES):
        b = bundle_init(**BUNDLE_KW)
        k = jnp.asarray(keys[i])
        rows.append(bundle_update(b, k, k, k, jnp.ones(BATCH, bool)))
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *rows)
    return stacked, keys


def test_fleet_merge_bit_identical_to_cluster_merge():
    stacked, _ = per_node_bundles()
    mesh = make_mesh(n_nodes=N_NODES)
    merged = make_fleet_merge(mesh)(stacked)

    # the PR-11 path, driven directly through the same shard_map shape
    def specs_like(tree, spec):
        return jax.tree.map(lambda _: spec, tree)

    from jax.sharding import PartitionSpec as P
    reference = jax.jit(shard_map(
        fleet_collective_merge, mesh=mesh,
        in_specs=(specs_like(stacked, P(NODE_AXIS)),),
        out_specs=specs_like(jax.tree.map(lambda x: x[0], stacked), P()),
        check_vma=False))(stacked)
    assert bundle_digest(merged) == bundle_digest(reference)


def test_fleet_merge_deterministic_and_placement_independent():
    stacked, _ = per_node_bundles(seed=3)
    mesh = make_mesh(n_nodes=N_NODES)
    merge = make_fleet_merge(mesh)
    d1 = bundle_digest(merge(stacked))
    d2 = bundle_digest(merge(stacked))
    assert d1 == d2
    # pre-placing the rows on the node axis (what each real host does
    # with make_array_from_process_local_data) changes nothing
    d3 = bundle_digest(merge(shard_over_nodes(mesh, stacked)))
    assert d1 == d3


def test_fleet_merge_integer_lanes_are_exact_sums():
    stacked, keys = per_node_bundles(seed=5)
    mesh = make_mesh(n_nodes=N_NODES)
    merged = make_fleet_merge(mesh)(stacked)
    # CMS psum = per-node table sum, HLL pmax = register max — exact
    np.testing.assert_array_equal(
        np.asarray(merged.cms.table),
        np.asarray(stacked.cms.table).sum(axis=0))
    np.testing.assert_array_equal(
        np.asarray(merged.hll.registers),
        np.asarray(stacked.hll.registers).max(axis=0))
    assert float(merged.events) == float(N_NODES * BATCH)


WORKER = textwrap.dedent("""
    import json, os, sys
    sys.path.insert(0, os.getcwd())
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    jax.config.update("jax_platforms", "cpu")

    coord, pid = sys.argv[1], int(sys.argv[2])
    from inspektor_gadget_tpu.parallel.distributed import (
        init_distributed, make_multihost_mesh, world_size,
    )
    init_distributed(coord, num_processes=2, process_id=pid)
    assert world_size() == 2

    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from inspektor_gadget_tpu.fleet.collective import (
        bundle_digest, make_fleet_merge,
    )
    from inspektor_gadget_tpu.ops import bundle_init, bundle_update
    from inspektor_gadget_tpu.parallel.mesh import NODE_AXIS

    mesh = make_multihost_mesh()
    n_nodes = mesh.shape[NODE_AXIS]  # 2 procs x 2 virtual devices
    rng = np.random.default_rng(0)
    keys = rng.zipf(1.3, (n_nodes, 256)).clip(1, 10_000).astype(
        np.uint32)
    rows = []
    for i in range(n_nodes):
        b = bundle_init(depth=4, log2_width=10, hll_p=8,
                        entropy_log2_width=7, k=16)
        k = jnp.asarray(keys[i])
        rows.append(bundle_update(b, k, k, k, jnp.ones(256, bool)))
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *rows)
    sharding = NamedSharding(mesh, P(NODE_AXIS))
    local = jax.tree.map(
        lambda x: jax.make_array_from_process_local_data(
            sharding, np.asarray(x)[pid * 2:(pid + 1) * 2]), stacked)
    try:
        merged = make_fleet_merge(mesh)(local)
    except Exception as e:
        if "Multiprocess computations aren't implemented" in str(e):
            print(json.dumps({"skip": str(e)}), flush=True)
            sys.exit(0)
        raise
    host_view = jax.tree.map(
        lambda a: np.asarray(a.addressable_shards[0].data), merged)
    print(json.dumps({"pid": pid,
                      "digest": bundle_digest(host_view),
                      "events": float(host_view.events)}))
""")


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_fleet_merge_digests_match(tmp_path):
    """Both hosts of a simulated 2-process DCN world must materialize
    the SAME fleet bundle — digest-compared across processes, the
    multi-host form of the tier's bit-identity contract."""
    coord = f"127.0.0.1:{_free_port()}"
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), coord, str(i)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            cwd=REPO)
        for i in range(2)
    ]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=220)
        assert p.returncode == 0, f"worker failed:\n{err[-3000:]}"
        line = [ln for ln in out.splitlines() if ln.startswith("{")][-1]
        outs.append(json.loads(line))
    skips = [o for o in outs if "skip" in o]
    if skips:
        pytest.skip("backend cannot run multiprocess collectives: "
                    f"{skips[0]['skip']}")
    assert outs[0]["digest"] == outs[1]["digest"]
    assert outs[0]["events"] == 4 * 256
