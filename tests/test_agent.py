"""Distributed runtime tests: real gRPC agents + client fan-out.

Models the reference's integration tier (SURVEY §4: deploy agents, run
kubectl-gadget, match JSON events) scaled to in-process agents on unix
sockets — 3 'nodes' on one host.
"""

import tempfile
import threading
import time
from pathlib import Path

import pytest

import inspektor_gadget_tpu.all_gadgets  # noqa: F401
from inspektor_gadget_tpu.agent.service import serve
from inspektor_gadget_tpu.agent.client import AgentClient
from inspektor_gadget_tpu.agent.stream import GadgetStream, LOST_MARKER
from inspektor_gadget_tpu.gadgets import GadgetContext, get
from inspektor_gadget_tpu.params import Params
from inspektor_gadget_tpu.snapshotcombiner import SnapshotCombiner


@pytest.fixture(scope="module")
def agents():
    servers = []
    targets = {}
    tmp = tempfile.mkdtemp()
    for i in range(3):
        addr = f"unix://{tmp}/agent{i}.sock"
        server, agent = serve(addr, node_name=f"node-{i}")
        servers.append(server)
        targets[f"node-{i}"] = addr
    yield targets
    for s in servers:
        s.stop(grace=0.5)


def test_catalog_roundtrip(agents):
    client = AgentClient(next(iter(agents.values())), "node-0")
    cat = client.get_catalog()
    names = {(g["category"], g["name"]) for g in cat["gadgets"]}
    assert ("trace", "exec") in names
    assert any(op["name"] == "tpusketch" for op in cat["operators"])
    client.close()


def test_single_node_stream_with_seq(agents):
    client = AgentClient(agents["node-1"], "node-1")
    rows = []
    res = client.run_gadget(
        "trace", "exec",
        {"gadget.source": "pysynthetic", "gadget.rate": "20000",
         "gadget.batch-size": "256"},
        timeout=1.0, on_json=lambda node, row: rows.append((node, row)),
    )
    assert res["error"] is None
    assert len(rows) > 50
    assert rows[0][0] == "node-1"
    assert rows[0][1]["comm"].startswith("proc-")
    # loss accounting contract (not zero-loss: under CPU contention the
    # server's bounded buffer may drop, as the reference's does —
    # service.go:160-167): every client-observed seq gap must be covered by
    # the server's drop count. Drops past the last delivered message (tail
    # eviction while the run winds down) legitimately show no gap, so
    # dropped > 0 with gaps == 0 is valid — the reverse is not.
    # The drop count rides the CONTROL_ACK trailer, and that trailer and the
    # end-of-stream sentinel after it are FORCED onto a possibly full queue:
    # each may evict one more queued record after the count was written
    # into the ACK's header, so the client may see up to two gaps more.
    assert res["gaps"] <= res["dropped"] + 2, "seq gaps exceed drop accounting"
    client.close()


def test_fanout_runtime_merges_nodes(agents):
    from inspektor_gadget_tpu.runtime import GrpcRuntime

    desc = get("trace", "exec")
    params = desc.params().to_params()
    params.set("source", "pysynthetic")
    params.set("rate", "5000")
    params.set("batch-size", "256")
    ctx = GadgetContext(desc, gadget_params=params, timeout=1.5)
    runtime = GrpcRuntime(dict(agents))
    events = []
    result = runtime.run_gadget(ctx, on_event=events.append)
    assert set(result.keys()) == {"node-0", "node-1", "node-2"}
    assert not result.errors()
    nodes_seen = {e.node for e in events}
    assert nodes_seen == {"node-0", "node-1", "node-2"}
    runtime.close()


def test_fanout_real_host_wide_window(agents):
    """The distributed plane carries REAL capture windows, not just the
    synthetic streams: trace/capabilities through the gRPC fan-out with a
    live unprivileged-chown workload must deliver denial rows from every
    node (each agent runs its own host-wide window)."""
    import os
    import shutil
    import subprocess

    from inspektor_gadget_tpu.runtime import GrpcRuntime
    from inspektor_gadget_tpu.sources.bridge import (audit_supported,
                                                     captrace_supported)
    if os.geteuid() != 0 or not shutil.which("setpriv"):
        pytest.skip("needs root + setpriv")
    if not (captrace_supported() or audit_supported()):
        pytest.skip("no host-wide capability window")

    target = f"/tmp/ig_fanout_cap_{os.getpid()}"
    open(target, "w").close()
    stop = threading.Event()

    def trigger():
        time.sleep(0.8)
        while not stop.is_set():
            subprocess.run(["setpriv", "--reuid", "65534", "--clear-groups",
                            "chown", "0:0", target],
                           check=False, stderr=subprocess.DEVNULL)
            stop.wait(0.25)

    t = threading.Thread(target=trigger)
    t.start()
    runtime = None
    try:
        desc = get("trace", "capabilities")
        params = desc.params().to_params()
        ctx = GadgetContext(desc, gadget_params=params, timeout=4.0)
        runtime = GrpcRuntime(dict(agents))
        events = []
        result = runtime.run_gadget(ctx, on_event=events.append)
    finally:
        if runtime is not None:
            runtime.close()
        stop.set()
        t.join()
        os.unlink(target)
    assert not result.errors(), result.errors()
    denials = [e for e in events
               if getattr(e, "cap", "") == "CHOWN"
               and getattr(e, "verdict", "") == "deny"]
    assert denials, f"{len(events)} events, no CHOWN denials"
    # every node observed the host-wide workload (shared kernel)
    assert {e.node for e in denials} == {"node-0", "node-1", "node-2"}


def test_fanout_node_filter(agents):
    from inspektor_gadget_tpu.runtime import GrpcRuntime

    desc = get("trace", "exec")
    params = desc.params().to_params()
    params.set("source", "pysynthetic")
    params.set("rate", "5000")
    rt_params = Params(GrpcRuntime(dict(agents)).params())
    rt_params.set("node", "node-2")
    ctx = GadgetContext(desc, gadget_params=params,
                        runtime_params=rt_params, timeout=1.0)
    runtime = GrpcRuntime(dict(agents))
    events = []
    result = runtime.run_gadget(ctx, on_event=events.append)
    assert set(result.keys()) == {"node-2"}
    assert {e.node for e in events} == {"node-2"}
    runtime.close()


def test_summary_stream_sketch_merge(agents):
    """Nodes stream sketch digests; client merges (the low-bandwidth path)."""
    client = AgentClient(agents["node-0"], "node-0")
    summaries = []
    res = client.run_gadget(
        "trace", "exec",
        {"gadget.source": "pysynthetic", "gadget.rate": "50000",
         "operator.tpusketch.enable": "true",
         "operator.tpusketch.log2-width": "12",
         "operator.tpusketch.hll-p": "10",
         "operator.tpusketch.harvest-interval": "300ms"},
        timeout=1.5, outputs=("summary",),
        on_summary=lambda node, s: summaries.append(s),
    )
    assert res["error"] is None
    assert summaries
    last = summaries[-1]
    assert last["events"] > 500
    assert last["heavy_hitters"]
    client.close()


def test_container_hook_rpc(agents):
    client = AgentClient(agents["node-0"], "node-0")
    r = client.add_container({"id": "h1", "name": "hooked", "pid": 1,
                              "mntns": 777777})
    assert r["ok"]
    r2 = client.remove_container("h1")
    assert r2["ok"]
    client.close()


def test_dump_state_debug_rpc(agents):
    client = AgentClient(agents["node-0"], "node-0")
    client.apply_trace({"metadata": {"name": "dump-t",
                                     "annotations": {}},
                        "spec": {"gadget": "trace/exec"}})
    state = client.dump_state()
    assert "threads" in state and state["threads"]
    # CRD-path state rides the same dump
    assert any(t["name"] == "dump-t" for t in state["traces"])
    client.delete_trace("dump-t")
    client.close()


# -- stream semantics (ref: stream.go tests) --------------------------------

def test_stream_replay_history():
    s = GadgetStream()
    for i in range(150):
        s.publish(i)
    sub = s.subscribe("late", replay=True)
    # only the last 100 retained
    items = list(sub.queue)
    assert len(items) == 100
    assert items[0] == 50 and items[-1] == 149


def test_stream_overrun_marks_loss():
    s = GadgetStream()
    sub = s.subscribe("slow", replay=False)
    for i in range(500):
        s.publish(i)
    items = list(sub.queue)
    assert LOST_MARKER in items
    assert len(items) <= 251


def test_snapshot_combiner_ttl():
    c = SnapshotCombiner(ttl_ticks=2)
    c.add_snapshot("node-0", ["a", "b"])
    c.add_snapshot("node-1", ["c"])
    assert sorted(c.get_snapshots()) == ["a", "b", "c"]
    # node-1 refreshes, node-0 ages out after ttl
    c.add_snapshot("node-1", ["c2"])
    out = c.get_snapshots()
    assert "c2" in out and "a" in out
    out = c.get_snapshots()
    assert out == ["c2"] or out == []  # node-0 aged out


def test_node_failure_isolated(agents):
    """Kill one agent mid-run: its node reports an error, others stream on
    (ref: CombinedGadgetResult partial results, runtime.go:42-79)."""
    import tempfile
    from inspektor_gadget_tpu.agent.service import serve as serve_agent
    from inspektor_gadget_tpu.runtime import GrpcRuntime

    tmp = tempfile.mkdtemp()
    addr = f"unix://{tmp}/doomed.sock"
    doomed_server, _ = serve_agent(addr, node_name="doomed")
    targets = dict(agents)
    targets["doomed"] = addr

    desc = get("trace", "exec")
    params = desc.params().to_params()
    params.set("source", "pysynthetic")
    # a light stream: four in-process agents and the client share one
    # interpreter, and a client that falls behind is evicted as stalled
    params.set("rate", "300")
    params.set("batch-size", "64")
    # the timeout is a ceiling for a loaded machine (under the agents'
    # 10 s stall eviction); the run ends as soon as every healthy node
    # has streamed AFTER the kill
    ctx = GadgetContext(desc, gadget_params=params, timeout=8.0)
    runtime = GrpcRuntime(targets)
    healthy = {"node-0", "node-1", "node-2"}
    killed = threading.Event()
    after_kill = set()

    def killer():
        time.sleep(0.6)
        doomed_server.stop(grace=0).wait()
        killed.set()

    def on_event(e):
        if killed.is_set():
            after_kill.add(e.node)
            if healthy <= after_kill:
                ctx.cancel()

    threading.Thread(target=killer, daemon=True).start()
    result = runtime.run_gadget(ctx, on_event=on_event)
    runtime.close()
    assert healthy <= set(result.keys())
    for n in healthy:
        assert result[n].error is None, result[n].error
    assert after_kill >= healthy
