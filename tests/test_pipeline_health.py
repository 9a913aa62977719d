"""Pipeline health plane (ISSUE 18): watermarks, starvation accounting,
alerting, fleet/doctor surfaces, perf-ledger extras — and the FREE
contract (digests and wire headers untouched by the plane).

The acceptance story under test: the BENCH_r04 starvation gap (a device
plane that drains the ring faster than one host thread refills it) is a
standing live signal on every instrumented run. The stager classifies
every tick as starved (host-bound) or saturated (device-bound); each
stage feeds a DDSketch host twin so summaries carry p50/p99 lag; the
`pipeline` block rides harvest summaries + DumpState without perturbing
a single digest byte; `pipeline_lag` turns a lag regression into exactly
one alert; `ig-tpu fleet lag` and the doctor row render it live.
"""

from __future__ import annotations

import json
import tempfile

import numpy as np
import pytest

import inspektor_gadget_tpu.all_gadgets  # noqa: F401
from inspektor_gadget_tpu.gadgets import GadgetContext, get
from inspektor_gadget_tpu.params import Collection
from inspektor_gadget_tpu.telemetry import registry as telemetry_registry
from inspektor_gadget_tpu.telemetry.pipeline import (
    LagSketch,
    PipelineStats,
    live_stats,
)

GADGET = "trace/exec"


@pytest.fixture(autouse=True)
def _release_instances():
    """Instances built outside a real gadget run never see
    post_gadget_run — drop them from the live table, drain their stagers
    and unregister BOTH stats sources so no gauge residue leaks into
    other test files."""
    from inspektor_gadget_tpu.operators import tpusketch
    # these tests assert an EMPTY live registry after their own runs, so
    # orphans of earlier files on this worker (instances that never saw
    # post_gadget_run) go first — which files share a worker is the
    # scheduler's business
    for orphan in live_stats():
        orphan.unregister()
    before = set(tpusketch._live)
    yield
    with tpusketch._live_mu:
        fresh = [rid for rid in list(tpusketch._live) if rid not in before]
        insts = [tpusketch._live.pop(rid) for rid in fresh]
    for inst in insts:
        if getattr(inst, "_stager", None) is not None:
            inst._stager.drain()
        for st in getattr(inst, "_lane_stagers", []):
            st.drain()
        inst._stats.unregister()
        inst._pstats.unregister()


def _pipeline_gauges() -> dict[str, float]:
    # the plane's counters (backpressure, the turn's stage seconds and
    # turns) only go up: teardown returns gauges to baseline, not those
    return {k: v for k, v in telemetry_registry.snapshot().items()
            if k.startswith("ig_pipeline_")
            and not k.split("{")[0].endswith("_total")}


# ---------------------------------------------------------------------------
# LagSketch: parity with the quantile plane's own bucket math
# ---------------------------------------------------------------------------

def test_lag_sketch_parity_with_dd_quantile_np():
    """The scalar-math host twin must read EXACTLY like dd_quantile_np
    over its own lanes — the health plane eats the quantile plane's
    dogfood, it does not fork the math."""
    from inspektor_gadget_tpu.ops.quantiles import dd_quantile_np

    rng = np.random.default_rng(18)
    sk = LagSketch()
    samples = rng.lognormal(np.log(1e-3), 1.5, 5000)
    samples[:100] = 0.0                      # idle ticks → zero bucket
    for v in samples:
        sk.add(float(v))
    assert sk.total == 5000 and sk.zeros == 100
    for q in (0.0, 0.25, 0.50, 0.90, 0.99, 0.999):
        ref = float(dd_quantile_np(sk.counts, sk.zeros, sk.total, q,
                                   alpha=sk.alpha, min_value=sk.min_value))
        assert abs(sk.quantile(q) - ref) < 1e-12, (q, sk.quantile(q), ref)
    # relative accuracy holds against the raw samples (alpha 1%, and the
    # ~0.2% extra from rank-vs-midpoint rounding at this sample count)
    pos = np.sort(samples[samples > 0])
    for q in (0.50, 0.99):
        true = float(np.quantile(samples, q))
        assert abs(sk.quantile(q) - true) / true < 0.03
    # empty + all-zeros sketches read 0.0, never NaN
    assert LagSketch().quantile(0.99) == 0.0
    z = LagSketch()
    z.add(0.0)
    assert z.quantile(0.5) == 0.0 and z.watermark == 0.0


def test_lag_sketch_clips_extremes_without_blowing_up():
    sk = LagSketch()
    sk.add(1e-12)        # below min_value → bucket 0
    sk.add(1e9)          # absurd lag → clipped to the last bucket
    assert sk.total == 2 and sk.zeros == 0
    assert sk.counts.sum() == 2
    assert sk.quantile(0.0) > 0.0


# ---------------------------------------------------------------------------
# PipelineStats: snapshot shape + gauge teardown discipline
# ---------------------------------------------------------------------------

def test_pipeline_stats_snapshot_shape_and_worst_lane():
    ps = PipelineStats("run-ph-shape", GADGET)
    ps.register()
    try:
        assert any(p.run_id == "run-ph-shape" for p in live_stats())
        ps.note_host_lag(0.002)
        ps.note_host_lag(0.004)
        ps.note_host_lag(0.010, lane=1)      # laggiest lane
        ps.note_device_lag(0.001)
        ps.note_starved()
        ps.note_starved()
        ps.note_saturated(0.005)
        ps.note_backpressure("pop", 2)
        ps.note_occupancy("h2d", 2, lane=1)
        ps.note_round()
        snap = ps.snapshot()
        # multi-lane stages report the WORST lane's view, summed counts
        assert snap["stages"]["pop"]["count"] == 3
        assert snap["stages"]["pop"]["watermark_s"] == 0.010
        assert snap["stages"]["pop"]["p99_s"] >= snap["stages"]["pop"]["p50_s"] > 0.0
        assert snap["host_lag_s"] == 0.010
        assert snap["device_lag_s"] == 0.001
        assert snap["starved"] == 2 and snap["saturated"] == 1
        assert snap["starved_ratio"] == pytest.approx(2 / 3)
        assert snap["stall_s"] == pytest.approx(0.005)
        # note_saturated books its stall as h2d backpressure too
        assert snap["backpressure"] == {"h2d": 1, "pop": 2}
        assert snap["occupancy"] == {"h2d:1": 2.0}
        assert snap["rounds"] == 1
        json.dumps(snap)                     # plain JSON-able, always
        # the shared gauges read the live values while registered
        g = _pipeline_gauges()
        assert g['ig_pipeline_stage_lag_seconds{stage="pop",lane="1"}'] == 0.010
        assert g['ig_pipeline_occupancy{stage="h2d",lane="1"}'] == 2.0
        assert g["ig_pipeline_starved_ratio"] == pytest.approx(2 / 3)
    finally:
        ps.unregister()
    # teardown discipline: every touched gauge back EXACTLY to baseline
    assert all(v == 0.0 for v in _pipeline_gauges().values()), \
        _pipeline_gauges()
    assert not any(p.run_id == "run-ph-shape" for p in live_stats())


def test_empty_stats_snapshot_is_all_zero():
    snap = PipelineStats("run-ph-empty").snapshot()
    assert snap["stages"] == {} and snap["starved_ratio"] == 0.0
    assert snap["host_lag_s"] == 0.0 and snap["device_lag_s"] == 0.0


# ---------------------------------------------------------------------------
# H2DStager: the starved/saturated tick classification is deterministic
# ---------------------------------------------------------------------------

def test_stager_classifies_starved_then_saturated_ticks():
    from inspektor_gadget_tpu.sources.staging import (
        H2DStager,
        PinnedBufferPool,
    )

    ps = PipelineStats("run-ph-stager")
    pool = PinnedBufferPool(64, lanes=2)
    stager = H2DStager(pool, depth=2, stats=ps)
    try:
        for i in range(5):
            blk = pool.get()
            devs = stager.stage(blk, [blk[0], blk[1]])
            stager.fence(devs[0])
        snap = ps.snapshot()
        # the first `depth` ticks land on an empty ring (starved — the
        # warmup guarantee the e2e starved_ratio > 0 assertion rides);
        # every later tick finds its slot occupied (saturated)
        assert snap["starved"] == 2 and snap["saturated"] == 3
        assert snap["starved_ratio"] == pytest.approx(2 / 5)
        assert snap["backpressure"]["h2d"] == 3
        assert snap["occupancy"]["h2d:0"] == 2.0   # ring full after warmup
        stager.drain()
        assert ps.snapshot()["occupancy"]["h2d:0"] == 0.0
    finally:
        ps.unregister()


# ---------------------------------------------------------------------------
# operator e2e: a real run carries the block, then leaves no residue
# ---------------------------------------------------------------------------

def _sketch_run_ctx(timeout: float, summaries: list) -> GadgetContext:
    desc = get("trace", "exec")
    params = desc.params().to_params()
    params.set("source", "pysynthetic")
    params.set("rate", "20000")
    params.set("batch-size", "256")
    from inspektor_gadget_tpu.operators.operators import get as get_op
    sp = get_op("tpusketch").instance_params().to_params()
    for k, v in (("enable", "true"), ("log2-width", "8"), ("hll-p", "6"),
                 ("entropy-log2-width", "6"), ("topk", "8"),
                 ("harvest-interval", "300ms")):
        sp.set(k, v)
    op_params = Collection()
    op_params["operator.tpusketch."] = sp
    return GadgetContext(desc, gadget_params=params,
                         operator_params=op_params, timeout=timeout,
                         extra={"on_sketch_summary": summaries.append})


def test_local_run_populates_pipeline_block_and_tears_down():
    from inspektor_gadget_tpu.runtime.local import LocalRuntime

    summaries: list = []
    for attempt in (1, 2):     # one retry: suite load can starve a short run
        result = LocalRuntime().run_gadget(
            _sketch_run_ctx(1.2 * attempt, summaries))
        assert not result.errors(), result.errors()
        if any(s.events for s in summaries):
            break
        summaries.clear()
    s = next(s for s in reversed(summaries) if s.events)
    pipe = s.pipeline
    assert pipe is not None
    # pysynthetic stamps pop_ts == oldest_ts at synthesis, so the pop
    # stage exists with ~zero lag and the h2d stage carries the real
    # staging+dispatch wait
    assert {"pop", "h2d"} <= set(pipe["stages"])
    assert pipe["stages"]["h2d"]["count"] > 0
    assert pipe["stages"]["h2d"]["watermark_s"] > 0.0
    assert pipe["stages"]["h2d"]["p99_s"] > 0.0
    # ring warmup makes starvation deterministic: the first `depth`
    # stage() calls always find an empty slot
    assert pipe["starved"] > 0
    assert pipe["starved_ratio"] > 0.0
    # `rounds` counts sharded-ingest dispatch rounds — 0 on this
    # single-chip path, but the key is always present for consumers
    assert pipe["rounds"] == 0
    # the run ended: no live stats, every shared gauge back to baseline
    assert not any(p.gadget == GADGET for p in live_stats())
    assert all(v == 0.0 for v in _pipeline_gauges().values()), \
        _pipeline_gauges()


# ---------------------------------------------------------------------------
# FREE: digests and wire headers are untouched by the plane
# ---------------------------------------------------------------------------

def test_summary_digest_ignores_pipeline_block():
    """summary_digest builds from a fixed whitelist — the pipeline block
    CANNOT perturb it, so sealed windows and `replay --verify` stay
    byte-identical with the plane on (the tentpole's FREE proof)."""
    from inspektor_gadget_tpu.capture.journal import summary_digest

    base = {"events": 100, "drops": 2, "distinct": 7.0, "entropy": 1.5,
            "epoch": 3, "heavy_hitters": [[1, 5], [2, 3]]}
    with_plane = dict(base, pipeline={
        "stages": {"pop": {"watermark_s": 0.01, "p50_s": 0.01,
                           "p99_s": 0.02, "count": 9}},
        "host_lag_s": 0.01, "device_lag_s": 0.002, "starved": 4,
        "saturated": 1, "starved_ratio": 0.8, "stall_s": 0.0,
        "backpressure": {}, "occupancy": {}, "rounds": 9})
    assert summary_digest(base) == summary_digest(with_plane)


def test_wire_encoding_only_when_present_and_roundtrip():
    from inspektor_gadget_tpu.agent import wire
    from inspektor_gadget_tpu.operators.tpusketch import SketchSummary

    plain = SketchSummary(events=10, drops=0, distinct=3.0,
                          entropy_bits=1.5, heavy_hitters=[(1, 5)], epoch=2)
    h, _ = wire.encode_summary(plain)
    assert "pipeline" not in h            # pre-plane headers byte-identical
    block = {"stages": {"h2d": {"watermark_s": 0.004, "p50_s": 0.003,
                                "p99_s": 0.008, "count": 12}},
             "host_lag_s": 0.0, "device_lag_s": 0.004, "starved": 2,
             "saturated": 10, "starved_ratio": 1 / 6, "stall_s": 0.01,
             "backpressure": {"h2d": 10}, "occupancy": {"h2d:0": 2.0},
             "rounds": 12}
    on = SketchSummary(events=10, drops=0, distinct=3.0, entropy_bits=1.5,
                       heavy_hitters=[(1, 5)], epoch=2, pipeline=block)
    h2, payload = wire.encode_summary(on)
    out = wire.decode_summary(h2, payload)
    assert out["pipeline"] == block


# ---------------------------------------------------------------------------
# alerts: the pipeline_lag detector kind
# ---------------------------------------------------------------------------

def test_pipeline_lag_rule_validation():
    from inspektor_gadget_tpu.alerts.rules import RuleError, load_rules

    rules = load_rules(json.dumps([{"id": "pl", "kind": "pipeline_lag",
                                    "factor": 3.0}]))
    assert rules[0].field == "host_lag"     # the default stage signal
    assert rules[0].threshold == 0.0        # threshold optional
    assert "pipeline health plane" in rules[0].describe()
    rules2 = load_rules(json.dumps([{"id": "pl", "kind": "pipeline_lag",
                                     "field": "starved_ratio",
                                     "factor": 2.0}]))
    assert rules2[0].field == "starved_ratio"
    with pytest.raises(RuleError, match="pipeline_lag watches"):
        load_rules(json.dumps([{"id": "pl", "kind": "pipeline_lag",
                                "field": "entropy", "factor": 2.0}]))


def test_pipeline_lag_fires_once_with_idle_immunity():
    """BENCH_r04 acceptance at the engine layer: healthy epochs build the
    baseline, an idle window (plane off / no traffic → 0.0) must NOT
    poison it, a 4x host-lag regression fires exactly once through the
    hysteresis machine, and staying regressed does not re-fire."""
    from inspektor_gadget_tpu.alerts.engine import AlertEngine
    from inspektor_gadget_tpu.alerts.rules import load_rules

    rules = load_rules(json.dumps([{
        "id": "lag", "kind": "pipeline_lag", "field": "host_lag",
        "factor": 2.0, "window": 3, "for": 0}]))
    eng = AlertEngine(rules, node="n0", gadget=GADGET, dry_run=True)
    base = {"events": 100, "drops": 0, "distinct": 5.0, "entropy": 1.0,
            "heavy_hitters": [], "anomaly": {}}

    def obs(epoch, host_lag, now):
        return eng.observe(
            {**base, "epoch": epoch,
             "pipeline": {"host_lag_s": host_lag,
                          "device_lag_s": host_lag / 4,
                          "starved_ratio": 0.5}}, now=now)

    transitions = []
    # 3 healthy epochs (~2ms), one idle window in the middle
    for i, lag in enumerate((0.0020, 0.0021, 0.0, 0.0019)):
        transitions += [(e.transition, i) for e in obs(i, lag, 10.0 * i)]
    assert transitions == []                # baseline warmup never fires
    evs = obs(4, 0.0080, 40.0)
    assert [e.transition for e in evs] == ["pending", "firing"]
    assert evs[-1].rule == "lag" and evs[-1].value == 0.0080
    evs2 = obs(5, 0.0082, 50.0)
    assert not any(e.transition == "firing" for e in evs2)
    eng.close()


def test_pipeline_lag_ignores_plane_off_summaries():
    from inspektor_gadget_tpu.alerts.engine import AlertEngine
    from inspektor_gadget_tpu.alerts.rules import load_rules

    rules = load_rules(json.dumps([{
        "id": "lag", "kind": "pipeline_lag", "factor": 1.1,
        "window": 2, "for": 0}]))
    eng = AlertEngine(rules, node="n0", gadget=GADGET, dry_run=True)
    base = {"events": 100, "drops": 0, "distinct": 5.0, "entropy": 1.0,
            "heavy_hitters": [], "anomaly": {}}
    evs = []
    for epoch in range(6):                   # plane off: no pipeline key
        evs += eng.observe({**base, "epoch": epoch}, now=10.0 * epoch)
    assert evs == []
    eng.close()


# ---------------------------------------------------------------------------
# CLI: ig-tpu fleet lag (stubbed request path + rendering)
# ---------------------------------------------------------------------------

class _LagArgs:
    remote = ""
    deadline = 3.0
    gadget = ""
    watch = 0.0
    iterations = 0
    output = "table"

    def __init__(self, **kv):
        for k, v in kv.items():
            setattr(self, k, v)


_STUB_ROW = {
    "run_id": "run-stub-000001", "gadget": GADGET,
    "stages": {"pop": {"watermark_s": 0.0005, "p50_s": 0.0004,
                       "p99_s": 0.0009, "count": 120},
               "h2d": {"watermark_s": 0.0020, "p50_s": 0.0018,
                       "p99_s": 0.0041, "count": 120}},
    "host_lag_s": 0.0005, "device_lag_s": 0.0020,
    "starved": 30, "saturated": 90, "starved_ratio": 0.25,
    "stall_s": 0.4, "backpressure": {"h2d": 90},
    "occupancy": {"h2d:0": 2.0}, "rounds": 120,
}


def _stub_client(rows):
    class _StubClient:
        def __init__(self, target, node, rpc_deadline=3.0):
            self.node = node

        def dump_state(self):
            return {"pipeline": rows}

        def close(self):
            pass
    return _StubClient


def test_fleet_lag_renders_table_and_json(monkeypatch, capsys):
    from inspektor_gadget_tpu.agent import client as agent_client
    from inspektor_gadget_tpu.cli.fleet import cmd_fleet_lag

    monkeypatch.setattr(agent_client, "AgentClient",
                        _stub_client([_STUB_ROW]))
    assert cmd_fleet_lag(_LagArgs(remote="n0=localhost:19999")) == 0
    out = capsys.readouterr().out
    assert "STAGE" in out and "STARVED" in out
    assert "pop" in out and "h2d" in out
    assert "run-stub-00000" in out           # rid column (14 chars)
    assert "2.0ms" in out and "4.1ms" in out  # h2d watermark + p99
    assert "500us" in out                     # sub-ms lags render in us
    assert "25%" in out
    # json mode carries the rows verbatim
    assert cmd_fleet_lag(_LagArgs(remote="n0=localhost:19999",
                                  output="json")) == 0
    doc = json.loads(capsys.readouterr().out)
    run = doc["agents"][0]["runs"][0]
    assert run["starved_ratio"] == 0.25
    assert run["stages"]["h2d"]["p99_s"] == 0.0041
    # --gadget filters to matching runs only
    assert cmd_fleet_lag(_LagArgs(remote="n0=localhost:19999",
                                  gadget="trace/open")) == 0
    assert "no instrumented runs" in capsys.readouterr().out


def test_fleet_lag_unreachable_node_is_rc1(monkeypatch, capsys):
    from inspektor_gadget_tpu.agent import client as agent_client
    from inspektor_gadget_tpu.cli.fleet import cmd_fleet_lag

    class _Boom:
        def __init__(self, target, node, rpc_deadline=3.0):
            raise OSError("connection refused")

    monkeypatch.setattr(agent_client, "AgentClient", _Boom)
    assert cmd_fleet_lag(_LagArgs(remote="n0=localhost:19999")) == 1
    assert "unreachable" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# real 2-node gRPC fleet: DumpState → fleet lag table + doctor row
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def agents():
    from inspektor_gadget_tpu.agent.service import serve
    servers, targets = [], {}
    tmp = tempfile.mkdtemp()
    for i in range(2):
        addr = f"unix://{tmp}/lag-agent{i}.sock"
        server, _ = serve(addr, node_name=f"lnode-{i}")
        servers.append(server)
        targets[f"lnode-{i}"] = addr
    yield targets
    for s in servers:
        s.stop(grace=0.5)


def test_fleet_lag_and_doctor_over_real_fleet(agents, capsys):
    from inspektor_gadget_tpu.cli.fleet import cmd_fleet_lag
    from inspektor_gadget_tpu.doctor import _probe_pipeline_health

    ps = PipelineStats("run-fleet-lag-1", GADGET)
    ps.register()
    try:
        ps.note_host_lag(0.003)
        ps.note_device_lag(0.0011)
        ps.note_starved()
        ps.note_saturated(0.002)
        ps.note_occupancy("h2d", 1)
        remote = ",".join(f"{n}={t}" for n, t in agents.items())
        # --watch with --iterations: the second poll computes rates from
        # count deltas (static run → 0/s, but the column renders)
        assert cmd_fleet_lag(_LagArgs(remote=remote, watch=0.05,
                                      iterations=2)) == 0
        out = capsys.readouterr().out
        for node in agents:
            assert node in out
        assert "run-fleet-lag-" in out
        assert "pop" in out and "h2d" in out and "50%" in out
        assert "0/s" in out                 # the delta-rate column
        # the doctor row reads the same live registry
        w = _probe_pipeline_health()
        assert w.name == "pipeline_health" and w.ok
        assert "run-flee" in w.detail and "starved 50%" in w.detail
        assert "3.0ms" in w.detail          # worst-stage lag watermark
    finally:
        ps.unregister()
    w2 = _probe_pipeline_health()
    assert w2.ok and "no live instrumented runs" in w2.detail


def test_dump_state_carries_pipeline_rows(agents):
    from inspektor_gadget_tpu.agent.client import AgentClient

    ps = PipelineStats("run-dump-1", GADGET)
    ps.register()
    try:
        ps.note_device_lag(0.004)
        client = AgentClient(next(iter(agents.values())), "lnode-0")
        try:
            rows = client.dump_state()["pipeline"]
        finally:
            client.close()
        row = next(r for r in rows if r.get("run_id") == "run-dump-1")
        assert row["gadget"] == GADGET
        assert row["stages"]["h2d"]["watermark_s"] == 0.004
    finally:
        ps.unregister()


# ---------------------------------------------------------------------------
# docs lint: the starved-claim pattern in check_perf_claims
# ---------------------------------------------------------------------------

def test_check_perf_claims_starved_pattern():
    from tools.check_perf_claims import Backing, check_claim, extract_claims

    # both spellings parse, targets are skipped, kinds don't cross-match
    claims = extract_claims(
        "the run sat 13% starved on the cpu harness\n"
        "fleet lag showed starved 97%\n"
        "aim for ≥90% starved coverage\n", "docs/performance.md")
    starved = [c for c in claims if c.kind == "starved_pct"]
    assert [c.lo for c in starved] == [13.0, 97.0, 90.0]
    assert starved[2].skipped.startswith("target")
    cpu13 = Backing(13.04, "cpu", False, "PERF.jsonl:1#starved_fraction",
                    kind="starved_pct")
    # backed + the line says "cpu" → clean
    assert check_claim(starved[0], [cpu13]) == ""
    # an ev/s backing with the same number may NOT back a starved claim
    assert "NO ledger" in check_claim(
        starved[0], [Backing(13.0, "cpu", False, "x")])
    # backed only by a CPU record but the line doesn't say so → violation
    assert "CPU" in check_claim(
        starved[1], [Backing(97.0, "cpu", False, "y",
                             kind="starved_pct")])


def test_ledger_backings_surface_starved_fraction(tmp_path):
    from tools.check_perf_claims import _ledger_backings

    p = tmp_path / "PERF.jsonl"
    p.write_text(json.dumps({
        "config": "harness.e2e", "value": 1e6, "unit": "ev/s",
        "provenance": {"platform": "cpu", "degraded": False},
        "extra": {"starved_fraction": 0.1304}}) + "\n")
    backs = _ledger_backings(p)
    sf = [b for b in backs if b.kind == "starved_pct"]
    assert len(sf) == 1
    assert sf[0].value == pytest.approx(13.04)
    assert sf[0].second_class                # cpu → needs labeling
    assert sf[0].source.endswith("#starved_fraction")
