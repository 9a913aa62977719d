"""Shared-run multiplexing acceptance (ISSUE 12): one gadget run, many
subscribers, graceful degradation under fan-out.

- K subscribers on one 2-node fleet run: each agent provably runs ONE
  gadget (run registry + active-runs gauge counted once per node), the
  healthy subscribers receive identical record streams (content-aligned
  batches, identical summaries per epoch) with contiguous per-subscriber
  seqs,
- a deliberately-stalled low-priority subscriber accumulates drops on
  ITS OWN queue (EV_DROP_NOTICE + ig_agent_subscriber_drops_total) and
  is EVICTED with a labeled terminal record while its peers stream on
  unaffected,
- detach-all starts the run-keepalive countdown and a re-attach within
  it resumes WITHOUT a gadget restart (same context, same stream state),
- admission control refuses typed (max-subscribers, memory-budget; low
  priority first),
- a subscriber-churn chaos round (testing/chaos.SubscriberChurn, some
  rounds leaving by proxy cut) leaves no leaked queues, threads, or
  lingering runs,
- the summary pub/sub tier delivers harvest summaries + sealed-window
  announcements with zero raw batches.
"""

from __future__ import annotations

import tempfile
import threading
import time

import pytest

import inspektor_gadget_tpu.all_gadgets  # noqa: F401
from inspektor_gadget_tpu.agent import wire
from inspektor_gadget_tpu.agent.client import AgentClient
from inspektor_gadget_tpu.agent.service import serve
from inspektor_gadget_tpu.gadgets import GadgetContext, get
from inspektor_gadget_tpu.params import Params
from inspektor_gadget_tpu.runtime.grpc_runtime import GrpcRuntime
from inspektor_gadget_tpu.telemetry import REGISTRY
from inspektor_gadget_tpu.testing.chaos import ChaosProxy, SubscriberChurn

pytestmark = pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")

RUN_PARAMS = {"gadget.source": "pysynthetic", "gadget.rate": "2000",
              "gadget.batch-size": "128"}


def _metric(name: str, **labels) -> float:
    total = 0.0
    for key, v in REGISTRY.snapshot().items():
        if key != name and not key.startswith(name + "{"):
            continue
        if all(f'{k}="{lv}"' in key for k, lv in labels.items()):
            total += v
    return total


@pytest.fixture(scope="module")
def shared_agents():
    """Two in-process agents on unix sockets."""
    tmp = tempfile.mkdtemp()
    servers, agents, targets = [], {}, {}
    for i in range(2):
        addr = f"unix://{tmp}/shared{i}.sock"
        server, agent = serve(addr, node_name=f"shnode-{i}")
        servers.append(server)
        agents[f"shnode-{i}"] = agent
        targets[f"shnode-{i}"] = addr
    yield {"agents": agents, "targets": targets}
    for s in servers:
        s.stop(grace=0.5)


class _Collector:
    """Per-subscriber stream capture: seqs, data-record content keys
    (batch payload bytes), and summaries keyed by epoch."""

    def __init__(self):
        self.seqs: list[int] = []
        self.content: list[bytes] = []
        self.summaries: dict[int, tuple] = {}
        self.stop = threading.Event()
        self.out: dict = {}

    def on_message(self, _node, seq, _t):
        self.seqs.append(seq)

    def on_batch(self, _node, batch):
        self.content.append(batch.cols["key_hash"].tobytes())

    def on_summary(self, _node, s):
        self.summaries[int(s["epoch"])] = (int(s["events"]),
                                           int(s["distinct"]))


def _aligned_overlap(a: list, b: list) -> int:
    """Length of the contiguous common window of two record streams
    (each subscriber joins the SAME pipeline at its own moment, so one
    stream must be a windowed suffix of the other)."""
    if not a or not b:
        return 0
    for first, second in ((a, b), (b, a)):
        if second[0] in first:
            i = first.index(second[0])
            n = min(len(first) - i, len(second))
            if first[i:i + n] == second[:n]:
                return n
    return 0


def test_shared_fleet_run_one_gadget_k_subscribers(shared_agents):
    """The tentpole: a 2-node fleet run with share=true; two extra
    subscribers per node ride the SAME gadget (one run per agent, the
    active-runs gauge counts 2 for the whole fleet), receive identical
    record streams, and their accounting is exact."""
    agents = shared_agents["agents"]
    targets = shared_agents["targets"]
    runs_before = _metric("ig_agent_active_runs")

    from inspektor_gadget_tpu.operators import operators as op_registry
    from inspektor_gadget_tpu.params import Collection

    runtime = GrpcRuntime(dict(targets))
    desc = get("trace", "exec")
    params = desc.params().to_params()
    params.set("source", "pysynthetic")
    # a light stream: this test asserts EXACT accounting (no subscriber
    # drops on six concurrent streams), which a loaded CI host can only
    # honour when the per-event JSON fan-out stays far under what the
    # Python consumers drain — overload behaviour has its own tests below
    params.set("rate", "300")
    params.set("batch-size", "64")
    op_params = Collection()
    sp = op_registry.get("tpusketch").instance_params().to_params()
    for k, v in (("enable", "true"), ("log2-width", "10"),
                 ("hll-p", "10"), ("harvest-interval", "500ms")):
        sp.set(k, v)
    op_params["operator.tpusketch."] = sp
    rp = Params(runtime.params())
    rp.set("share", "true")
    rp.set("run-keepalive", "1s")
    ctx = GadgetContext(desc, gadget_params=params, operator_params=op_params,
                        runtime_params=rp, timeout=10.0)
    events = []
    fleet_done = threading.Event()
    fleet_holder: dict = {}

    def fleet_run():
        fleet_holder["result"] = runtime.run_gadget(
            ctx, on_event=events.append, on_batch=lambda b: None,
            on_summary=lambda n, s: None)
        fleet_done.set()

    threading.Thread(target=fleet_run, daemon=True).start()

    # wait until the shared run is registered on both agents, then
    # attach two extra subscribers per node as fast as possible (the
    # sketch warmup keeps the pipeline quiet far longer than this)
    def live_run(agent):
        for st in agent._streams.values():
            if st.shared and not st.done:
                return st
        return None

    deadline = time.monotonic() + 20.0
    while time.monotonic() < deadline:
        if all(live_run(a) is not None for a in agents.values()):
            break
        time.sleep(0.02)
    run_states = {n: live_run(a) for n, a in agents.items()}
    assert all(run_states.values()), "shared runs never registered"

    subs: dict[tuple, _Collector] = {}
    threads = []
    for node, target in targets.items():
        for j in range(2):
            col = _Collector()
            subs[(node, j)] = col

            def pump(target=target, node=node, col=col):
                client = AgentClient(target, node)
                col.out = client.run_gadget(
                    "", "", attach_to=run_states[node].run_id,
                    subscriber={"priority": "high", "queue": 4096},
                    on_message=col.on_message, on_batch=col.on_batch,
                    on_summary=col.on_summary, stop_event=col.stop)
                client.close()

            t = threading.Thread(target=pump, daemon=True)
            t.start()
            threads.append(t)

    # ONE gadget per agent while K=3 subscribers ride each node
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        if all(st.live_subscribers() >= 3 for st in run_states.values()):
            break
        time.sleep(0.05)
    for node, st in run_states.items():
        assert st.live_subscribers() >= 3, (node, st.subscriber_rows())
        assert len(agents[node]._runs) == 1, \
            f"{node} runs a private gadget per subscriber"
    assert _metric("ig_agent_active_runs") - runs_before == 2.0
    assert _metric("ig_agent_run_subscribers",
                   run=run_states["shnode-0"].run_id) >= 3.0

    # let data flow to every subscriber, then detach the extras cleanly
    deadline = time.monotonic() + 15.0
    while time.monotonic() < deadline:
        if all(len(c.content) >= 6 and len(c.summaries) >= 2
               for c in subs.values()):
            break
        time.sleep(0.1)
    for col in subs.values():
        col.stop.set()
    for t in threads:
        t.join(timeout=20.0)
    assert not fleet_done.is_set() or fleet_holder.get("result") is not None

    for (node, j), col in subs.items():
        out = col.out
        assert out["error"] is None, (node, j, out["error"])
        assert out["attach"] and out["attach"]["shared"] is True
        # exact per-subscriber accounting: contiguous seqs, no drops
        assert col.seqs == list(range(1, len(col.seqs) + 1)), (node, j)
        assert out["records"] == out["last_seq"] and out["gaps"] == 0
        assert out["sub_drops"] == 0 and out["evicted"] is False
        assert len(col.content) >= 6, (node, j, len(col.content))

    # identical record streams per node: the two subscribers' batch
    # sequences align on a long contiguous window, and their summaries
    # agree exactly on every epoch both observed
    for node in targets:
        a, b = subs[(node, 0)], subs[(node, 1)]
        overlap = _aligned_overlap(a.content, b.content)
        assert overlap >= min(len(a.content), len(b.content)) - 1 >= 5, \
            (node, len(a.content), len(b.content), overlap)
        common = set(a.summaries) & set(b.summaries)
        assert common, "no common summary epochs"
        for ep in common:
            assert a.summaries[ep] == b.summaries[ep], (node, ep)

    # the fleet run itself ends clean and labeled shared-aware
    assert fleet_done.wait(30.0)
    result = fleet_holder["result"]
    assert not result.errors(), result.errors()
    assert result.partial is False
    assert result.overloaded() == {}
    for node, r in result.items():
        assert r.records + r.gaps == r.last_seq, (node, r)
        assert r.sub_drops == 0 and not r.evicted
    runtime.close()

    # detach-all + keepalive expiry: the agents' gauges return to
    # baseline and nothing lingers
    deadline = time.monotonic() + 15.0
    while time.monotonic() < deadline:
        if _metric("ig_agent_active_runs") == runs_before:
            break
        time.sleep(0.1)
    assert _metric("ig_agent_active_runs") == runs_before


def test_stalled_low_priority_subscriber_dropped_and_evicted(shared_agents):
    """Overload protection: a low-priority subscriber whose client stops
    draining accumulates drops on ITS OWN 4-deep queue, is evicted after
    its stall window with a labeled terminal record, and the healthy
    peer on the same run never sees a gap, a drop, or a stall."""
    agents = shared_agents["agents"]
    target = shared_agents["targets"]["shnode-1"]
    evictions_before = _metric("ig_agent_subscriber_evictions_total")

    owner_stop = threading.Event()
    owner_holder: dict = {}
    params = dict(RUN_PARAMS)
    params["gadget.rate"] = "3000"     # distinct share key per test
    params["gadget.batch-size"] = "256"

    def owner():
        c = AgentClient(target, "shnode-1")
        owner_holder["out"] = c.run_gadget(
            "trace", "exec", params, timeout=0.0, run_id="evict-e2e",
            share=True, keepalive=1.0, outputs=("batch",),
            subscriber={"priority": "high"},
            on_message=lambda *_: None, stop_event=owner_stop)
        c.close()

    t_owner = threading.Thread(target=owner, daemon=True)
    t_owner.start()
    deadline = time.monotonic() + 20.0
    while time.monotonic() < deadline:
        st = agents["shnode-1"]._streams.get("evict-e2e")
        if st is not None and st.index > 0:
            break
        time.sleep(0.05)
    assert st is not None and not st.done, "shared run never produced"

    # the victim: low priority, tiny queue, short stall budget, and a
    # client whose handler BLOCKS on a gate — the wedged-dashboard case.
    # The gate opens only after the agent has evicted it, so the client
    # can then drain its transport buffer and observe the labeled
    # terminal record.
    gate = threading.Event()
    victim_holder: dict = {}

    def victim():
        c = AgentClient(target, "victim")
        victim_holder["out"] = c.run_gadget(
            "", "", attach_to="evict-e2e",
            subscriber={"priority": "low", "queue": 4,
                        "evict_after": 0.8,
                        "drop_policy": "drop-oldest"},
            on_message=lambda *_: gate.wait(60.0))
        c.close()

    t_victim = threading.Thread(target=victim, daemon=True)
    t_victim.start()

    # a healthy peer riding the same run throughout the eviction
    peer = _Collector()

    def peer_pump():
        c = AgentClient(target, "peer")
        peer.out = c.run_gadget(
            "", "", attach_to="evict-e2e",
            subscriber={"priority": "normal", "queue": 4096},
            on_message=peer.on_message, stop_event=peer.stop)
        c.close()

    t_peer = threading.Thread(target=peer_pump, daemon=True)
    t_peer.start()

    # wait for the agent to evict the wedged subscriber, then open the
    # gate so the client can drain to the terminal record
    deadline = time.monotonic() + 45.0
    evicted_row = None
    while time.monotonic() < deadline:
        rows = [s for s in st.subscriber_rows()
                if s["priority"] == "low" and s["evicted"]]
        if rows:
            evicted_row = rows[0]
            break
        time.sleep(0.1)
    assert evicted_row is not None, \
        f"agent never evicted the wedged subscriber: {st.subscriber_rows()}"
    assert evicted_row["drops"] > 0, evicted_row
    gate.set()

    t_victim.join(timeout=60.0)
    assert not t_victim.is_alive(), "evicted subscriber stream never ended"
    out = victim_holder["out"]
    assert out["evicted"] is True
    assert "evicted" in (out["error"] or "")
    assert out["sub_drops"] > 0, "no drops accounted before eviction"
    assert _metric("ig_agent_subscriber_evictions_total") \
        >= evictions_before + 1.0
    assert _metric("ig_agent_subscriber_drops_total", run="evict-e2e",
                   policy="drop-oldest", **{"class": "low"}) \
        >= float(out["sub_drops"])

    # the gadget and the peer never noticed
    st = agents["shnode-1"]._streams.get("evict-e2e")
    assert st is not None and not st.done, "eviction hurt the shared run"
    time.sleep(0.5)
    peer.stop.set()
    t_peer.join(timeout=20.0)
    assert peer.out["error"] is None
    assert peer.out["sub_drops"] == 0 and peer.out["evicted"] is False
    assert peer.seqs == list(range(1, len(peer.seqs) + 1))
    assert peer.out["records"] == peer.out["last_seq"]
    # eviction shows in the DumpState subscriber rows (fleet runs view)
    rows = {s["sub_id"]: s for s in st.subscriber_rows()}
    assert any(s["evicted"] and s["priority"] == "low"
               for s in rows.values()), rows
    # ...and on the operator CLI: `ig-tpu fleet runs` labels the run's
    # drops and eviction — no silently-partial subscriber stream
    import contextlib
    import io

    from inspektor_gadget_tpu.cli.main import main as cli_main
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = cli_main(["fleet", "runs", "--remote",
                       f"shnode-1={target}"])
    assert rc == 0
    cli_out = buf.getvalue()
    line = next(ln for ln in cli_out.splitlines() if "evict-e2e" in ln)
    cols = line.split()
    assert "serving" in line
    # DROPS and EVICT columns are nonzero on the labeled row
    assert int(cols[-3]) >= out["sub_drops"] and int(cols[-2]) >= 1, line
    owner_stop.set()
    t_owner.join(timeout=20.0)
    assert owner_holder["out"]["error"] is None


def test_detach_all_keepalive_reattach_without_restart(shared_agents):
    """Dashboard churn must not thrash capture: when every subscriber
    leaves, the gadget keeps running for run-keepalive seconds; a
    re-attach inside the window rides the SAME run (same context object,
    same stream state, subscriber count back up) with no restart."""
    agents = shared_agents["agents"]
    target = shared_agents["targets"]["shnode-0"]
    stop1 = threading.Event()
    h1: dict = {}

    ka_params = dict(RUN_PARAMS, **{"gadget.rate": "2100"})

    def first():
        c = AgentClient(target, "ka-1")
        h1["out"] = c.run_gadget(
            "trace", "exec", ka_params, timeout=0.0, run_id="ka-e2e",
            share=True, keepalive=3.0,
            on_message=lambda *_: None, stop_event=stop1)
        c.close()

    t1 = threading.Thread(target=first, daemon=True)
    t1.start()
    deadline = time.monotonic() + 20.0
    st = None
    while time.monotonic() < deadline:
        st = agents["shnode-0"]._streams.get("ka-e2e")
        if st is not None and st.index > 0:
            break
        time.sleep(0.05)
    assert st is not None
    ctx_before = agents["shnode-0"]._runs.get("ka-e2e")
    assert ctx_before is not None

    # detach-all: the lone subscriber leaves; keepalive holds the run
    stop1.set()
    t1.join(timeout=20.0)
    assert h1["out"]["error"] is None
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and st.is_attached():
        time.sleep(0.05)
    assert not st.is_attached()
    assert not st.done, "gadget stopped instead of keeping alive"
    assert st.keepalive_remaining() > 0.0
    assert st.live_subscribers() == 0

    # re-attach within the window: same run, same context — no restart
    col = _Collector()
    h2: dict = {}

    def second():
        c = AgentClient(target, "ka-2")
        h2["out"] = c.run_gadget(
            "trace", "exec", ka_params, timeout=0.0, run_id="ignored",
            share=True,  # same (gadget, params, outputs) key → attach
            on_message=col.on_message, stop_event=col.stop)
        c.close()

    t2 = threading.Thread(target=second, daemon=True)
    t2.start()
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline and not col.seqs:
        time.sleep(0.05)
    assert col.seqs, "re-attached subscriber got no records"
    assert agents["shnode-0"]._runs.get("ka-e2e") is ctx_before, \
        "keepalive re-attach restarted the gadget"
    assert agents["shnode-0"]._streams.get("ka-e2e") is st
    assert st.live_subscribers() == 1
    col.stop.set()
    t2.join(timeout=20.0)
    assert h2["out"]["error"] is None
    assert h2["out"]["attach"]["run_id"] == "ka-e2e"
    assert h2["out"]["attach"]["shared"] is True

    # last detach again → keepalive expiry actually stops the gadget
    deadline = time.monotonic() + 15.0
    while time.monotonic() < deadline and not st.done:
        time.sleep(0.1)
    assert st.done, "keepalive expiry never stopped the gadget"


def test_admission_control_refuses_typed(shared_agents):
    """max-subscribers and the per-run subscriber budget refuse with a
    TYPED reason the client surfaces; low priority is refused at a
    budget level where high is still admitted."""
    target = shared_agents["targets"]["shnode-0"]
    refused_before = _metric("ig_agent_attach_refused_total",
                             reason="max-subscribers")
    stop = threading.Event()
    holder: dict = {}

    def owner():
        c = AgentClient(target, "adm-owner")
        holder["out"] = c.run_gadget(
            "trace", "exec", dict(RUN_PARAMS, **{"gadget.rate": "1900"}),
            timeout=0.0, run_id="adm-e2e",
            share=True, keepalive=0.2, max_subscribers=2, sub_budget=2048,
            subscriber={"queue": 1024, "priority": "high"},
            on_message=lambda *_: None, stop_event=stop)
        c.close()

    t = threading.Thread(target=owner, daemon=True)
    t.start()
    client = AgentClient(target, "adm-probe")
    deadline = time.monotonic() + 20.0
    while time.monotonic() < deadline:
        if any(r["run_id"] == "adm-e2e" for r in client.shared_runs()):
            break
        time.sleep(0.05)

    # budget: 1024 (owner) of 2048 used. A low-priority 512-queue would
    # reach 1536 > 60% of 2048 (=1228) → refused; the same queue at
    # high priority fits (≤ 2048) → admitted.
    low = client.run_gadget("", "", attach_to="adm-e2e",
                            subscriber={"priority": "low", "queue": 512},
                            timeout=5.0)
    assert low["attach_refused"] == "memory-budget", low
    assert "attach refused" in (low["error"] or "")
    assert _metric("ig_agent_attach_refused_total",
                   reason="memory-budget") >= 1.0

    keep = threading.Event()
    high_holder: dict = {}

    def high_sub():
        c2 = AgentClient(target, "adm-high")
        high_holder["out"] = c2.run_gadget(
            "", "", attach_to="adm-e2e",
            subscriber={"priority": "high", "queue": 512},
            on_message=lambda *_: None, stop_event=keep)
        c2.close()

    th = threading.Thread(target=high_sub, daemon=True)
    th.start()
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        rows = [r for r in client.shared_runs()
                if r["run_id"] == "adm-e2e"]
        if rows and rows[0]["live_subscribers"] >= 2:
            break
        time.sleep(0.05)
    assert rows and rows[0]["live_subscribers"] == 2, rows

    # the run is now at max-subscribers=2: ANY further admission refuses
    third = client.run_gadget("", "", attach_to="adm-e2e",
                              subscriber={"priority": "high"},
                              timeout=5.0)
    assert third["attach_refused"] == "max-subscribers", third
    assert _metric("ig_agent_attach_refused_total",
                   reason="max-subscribers") >= refused_before + 1.0
    # malformed options refuse loudly CLIENT-side before the wire
    with pytest.raises(ValueError):
        client.run_gadget("", "", attach_to="adm-e2e",
                          subscriber={"priority": "vip"})
    client.close()
    keep.set()
    th.join(timeout=20.0)
    assert high_holder["out"]["error"] is None
    stop.set()
    t.join(timeout=20.0)
    assert holder["out"]["error"] is None


def test_subscriber_churn_leaves_no_leaks(shared_agents):
    """The chaos round: attach/hold/detach churn (every 3rd round
    leaving by proxy cut) against one shared run — the run survives
    every round, and afterwards nothing lingers: no stream states, no
    leaked subscriber queues, thread count back to baseline."""
    agents = shared_agents["agents"]
    target = shared_agents["targets"]["shnode-1"]
    proxy = ChaosProxy(target)
    stop = threading.Event()
    holder: dict = {}
    baseline_threads = threading.active_count()

    def owner():
        c = AgentClient(target, "churn-owner")
        holder["out"] = c.run_gadget(
            "trace", "exec", dict(RUN_PARAMS, **{"gadget.rate": "1800"}),
            timeout=0.0, run_id="churn-e2e",
            share=True, keepalive=0.6,
            on_message=lambda *_: None, stop_event=stop)
        c.close()

    t = threading.Thread(target=owner, daemon=True)
    t.start()
    deadline = time.monotonic() + 20.0
    while time.monotonic() < deadline:
        st = agents["shnode-1"]._streams.get("churn-e2e")
        if st is not None and st.index > 0:
            break
        time.sleep(0.05)
    assert st is not None

    churn = SubscriberChurn(proxy.target, "churn-e2e", node="churner",
                            proxy=proxy,
                            subscriber={"priority": "normal",
                                        "queue": 256})
    churn.run(6, hold=0.4, cut_every=3)
    proxy.close()
    assert churn.rounds == 6 and churn.cuts == 2
    assert churn.acks >= 4, "clean rounds must ack their attach"
    assert not churn.errors, churn.errors
    assert not st.done, "subscriber churn killed the shared run"

    stop.set()
    t.join(timeout=20.0)
    assert holder["out"]["error"] is None

    # drain: keepalive + retire window pass; registries and threads
    # return to baseline — no leaked queues, threads, or lingering runs
    deadline = time.monotonic() + 20.0
    while time.monotonic() < deadline:
        if "churn-e2e" not in agents["shnode-1"]._streams \
                and threading.active_count() <= baseline_threads + 4:
            break
        time.sleep(0.2)
    assert "churn-e2e" not in agents["shnode-1"]._streams, \
        "stream state leaked past its retire window"
    assert threading.active_count() <= baseline_threads + 4, \
        "subscriber churn leaked threads"
    assert _metric("ig_agent_run_subscribers", run="churn-e2e") == 0.0


def test_summary_tier_gets_summaries_never_batches(shared_agents):
    """The summary pub/sub tier: a tier=summary subscriber on a shared
    run with history enabled receives harvest summaries and
    sealed-window announcements from the ONE shared harvest — and not a
    single raw row/batch/log message."""
    import os
    agents = shared_agents["agents"]
    target = shared_agents["targets"]["shnode-0"]
    from inspektor_gadget_tpu.history import HISTORY
    hist = tempfile.mkdtemp()
    HISTORY.set_base_dir(hist)
    stop = threading.Event()
    holder: dict = {}
    params = dict(RUN_PARAMS)
    params.update({"operator.tpusketch.enable": "true",
                   "operator.tpusketch.log2-width": "10",
                   "operator.tpusketch.hll-p": "10",
                   "operator.tpusketch.harvest-interval": "400ms",
                   "operator.tpusketch.history": "true",
                   "operator.tpusketch.history-interval": "0",
                   "operator.tpusketch.history-log2-width": "10",
                   "operator.tpusketch.history-slots": "4"})

    def owner():
        c = AgentClient(target, "sum-owner")
        holder["out"] = c.run_gadget(
            "trace", "exec", params, timeout=0.0, run_id="summary-e2e",
            share=True, keepalive=0.5,
            outputs=("json", "batch", "summary"),
            on_message=lambda *_: None, stop_event=stop)
        c.close()

    t = threading.Thread(target=owner, daemon=True)
    t.start()
    deadline = time.monotonic() + 20.0
    while time.monotonic() < deadline:
        st = agents["shnode-0"]._streams.get("summary-e2e")
        if st is not None and not st.done:
            break
        time.sleep(0.05)
    assert st is not None

    # the cheap consumer: a GrpcRuntime-level summary subscription
    runtime = GrpcRuntime({"shnode-0": target})
    summaries: list = []
    windows: list = []
    sub_stop = threading.Event()
    # subscribed until a summary AND a window announcement arrived (the
    # first harvest compiles; under six workers that can take seconds),
    # the timer only a ceiling
    ceiling = threading.Timer(30.0, sub_stop.set)
    ceiling.start()

    def seen(into: list, item) -> None:
        into.append(item)
        if summaries and windows:
            sub_stop.set()

    res = runtime.subscribe_summaries(
        gadget="trace/exec",
        on_summary=lambda n, s: seen(summaries, s),
        on_window=lambda n, w: seen(windows, w),
        stop_event=sub_stop)
    ceiling.cancel()
    runtime.close()
    out = res["shnode-0"]
    assert out.get("error") is None, out
    assert out["attach"] and out["attach"]["shared"] is True
    assert summaries, "summary tier delivered no summaries"
    assert windows, "summary tier delivered no window announcements"
    assert all(w.get("digest") and w.get("events", 0) >= 0
               for w in windows)
    # zero raw records reached this subscriber: every seq-bearing
    # message it got was summary-tier (the out['records'] count equals
    # what the summary/window/notice handlers saw, and no batch handler
    # even existed to call)
    assert out["records"] >= len(summaries) + len(windows)
    rows = {s["sub_id"]: s for r in [agents["shnode-0"]._streams[
        "summary-e2e"]] for s in r.subscriber_rows()}
    tier_rows = [s for s in rows.values() if s["tier"] == "summary"]
    assert tier_rows and all(s["drops"] == 0 for s in tier_rows)

    stop.set()
    t.join(timeout=20.0)
    assert holder["out"]["error"] is None
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline and not st.done:
        time.sleep(0.1)
    HISTORY.close_all()
    HISTORY.set_base_dir(None)
    assert os.path.isdir(hist)


# ---------------------------------------------------------------------------
# SharedRun-level regressions (review findings): anonymous-resume routing
# and detached-subscriber expiry
# ---------------------------------------------------------------------------

def test_anonymous_resume_prefers_detached_subscriber():
    """A resume without sub_id (PR-8 wire compat) must resolve to a
    DETACHED subscriber — picking the attached primary would hijack a
    live peer's stream and silently end it."""
    from inspektor_gadget_tpu.agent.service import SharedRun

    run = SharedRun("route-run", "trace/route", shared=True,
                    keepalive=5.0, node="t")
    a = run.admit({"queue": 64})
    run.attach_subscriber(a, 0)
    b = run.admit({"queue": 64})
    _qb, gen_b, _ack = run.attach_subscriber(b, 0)
    for _ in range(5):
        run.push(wire.EV_PAYLOAD_JSON, {"node": "t"}, b"x")
    run.detach(b, gen_b)
    assert a.attached and not b.attached

    resolved = run.resume("", b.seq)
    assert resolved is not None
    sub, _q, _gen, ack = resolved
    assert sub is b, "anonymous resume hijacked the attached primary"
    assert ack["sub_id"] == b.sub_id
    assert a.attached, "the live peer must be untouched"
    # a named resume still routes precisely
    resolved2 = run.resume(a.sub_id, a.seq)
    assert resolved2 is not None and resolved2[0] is a
    run.finish()


def test_detached_subscriber_expires_and_frees_its_slot():
    """A subscriber that disconnects and never resumes must not hold a
    max-subscribers slot (or budget capacity) for the life of the run:
    past the resume window (`linger`) it is expired-and-left, and a
    fresh admission succeeds where it would have been refused."""
    from inspektor_gadget_tpu.agent.service import SharedRun

    run = SharedRun("expire-run", "trace/expire", shared=True,
                    linger=0.2, keepalive=5.0, max_subscribers=2,
                    sub_budget=1 << 20, node="t")
    a = run.admit({"queue": 64})
    run.attach_subscriber(a, 0)
    b = run.admit({"queue": 64})
    _qb, gen_b, _ack = run.attach_subscriber(b, 0)
    run.detach(b, gen_b)

    # at capacity: a third admission refuses while the ghost lingers
    refused = run.admit({"queue": 64})
    assert isinstance(refused, dict) and \
        refused["reason"] == "max-subscribers"

    time.sleep(0.3)
    run.push(wire.EV_PAYLOAD_JSON, {"node": "t"}, b"x")
    assert b.left, "detached subscriber never expired past its window"
    # the ghost's resume answers gone (→ unknown_run upstream), and the
    # freed slot admits a live client
    assert run.resume(b.sub_id, 0) is None
    c = run.admit({"queue": 64})
    assert not isinstance(c, dict), c
    assert run.live_subscribers() == 2
    run.finish()
