"""Latency quantile plane (ISSUE 16): operator, fleet, alerting, perf
surfaces.

The acceptance story under test: a fleet that already answers "who is
heavy" (count planes) answers "what got slower" from the same fused
pass. The value lane rides the folded staging block into a DDSketch
grid plane; harvest summaries carry p50/p90/p99/p99.9 with <= alpha
relative error; sealed windows carry per-window bucket deltas that
re-merge bit-exactly across nodes; `quantile_shift` turns a percentile
regression into exactly one alert; and the plane OFF leaves every wire
byte exactly as it was before the plane existed.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

import inspektor_gadget_tpu.all_gadgets  # noqa: F401
from inspektor_gadget_tpu.gadgets import GadgetContext, get
from inspektor_gadget_tpu.history import HISTORY, answer_query, decode_frames
from inspektor_gadget_tpu.operators.operators import get as get_op
from inspektor_gadget_tpu.params import ParamError
from inspektor_gadget_tpu.sources.batch import EventBatch
from inspektor_gadget_tpu.telemetry import registry as telemetry_registry

GADGET = "trace/exec"


@pytest.fixture(autouse=True)
def _release_instances():
    """Instances built outside a real gadget run never see
    post_gadget_run — drop them from the live table (checkpoint_all
    iterates it) and drain their stagers (the h2d inflight gauge) so no
    state leaks into other test files."""
    from inspektor_gadget_tpu.operators import tpusketch
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


@pytest.fixture()
def fleet_store(tmp_path):
    HISTORY.set_base_dir(str(tmp_path))
    yield str(tmp_path)
    HISTORY.close_all()
    HISTORY.set_base_dir(None)


def _make_instance(extra_params: dict, node: str = "",
                   extra_ctx: dict | None = None):
    desc = get("trace", "exec")
    ctx = GadgetContext(desc, extra=dict(extra_ctx or {}))
    if node:
        ctx.extra["node"] = node
    op = get_op("tpusketch")
    p = op.instance_params().to_params()
    p.set("enable", "true")
    p.set("depth", "3")
    p.set("log2-width", "10")
    p.set("hll-p", "8")
    p.set("entropy-log2-width", "6")
    p.set("topk", "8")
    p.set("harvest-interval", "1h")
    for k, v in extra_params.items():
        p.set(k, v)
    return op.instantiate(ctx, None, p)


def _batch(keys64: np.ndarray, aux1: np.ndarray | None = None
           ) -> EventBatch:
    b = EventBatch.alloc(len(keys64), with_comm=False)
    b.cols["key_hash"][:] = keys64
    if aux1 is not None:
        b.cols["aux1"][:] = aux1
    b.count = len(keys64)
    return b


def _latencies(rng, n, median_ns=50_000.0, sigma=0.8):
    return rng.lognormal(np.log(median_ns), sigma, n).astype(np.uint64)


# ---------------------------------------------------------------------------
# param validation matrix
# ---------------------------------------------------------------------------

def test_param_error_matrix():
    op = get_op("tpusketch")

    def params(**kv):
        p = op.instance_params().to_params()
        p.set("enable", "true")
        for k, v in kv.items():
            p.set(k, v)
        return p

    # alpha grammar answers at the params layer (set-time validator)
    for bad in ("0", "-0.01", "0.31", "xx"):
        with pytest.raises(ParamError):
            params(**{"quantile-alpha": bad})
    # cross-param rules answer loudly at instantiation
    with pytest.raises(ParamError, match="needs 'quantiles true'"):
        _make_instance({"quantile-alpha": "0.05"})
    with pytest.raises(ParamError, match="needs 'quantiles true'"):
        _make_instance({"quantile-field": "mntns"})
    with pytest.raises(ParamError, match="not a .*column|wire column"):
        _make_instance({"quantiles": "true", "quantile-field": "latency"})
    # a valid config instantiates with the plane allocated
    inst = _make_instance({"quantiles": "true", "quantile-alpha": "0.02"})
    assert inst.enabled and inst.bundle.quantiles is not None
    assert inst._qt_alpha == 0.02 and inst._qt_field == "aux1"
    # plane off: the bundle carries NO quantile state at all
    off = _make_instance({})
    assert off.bundle.quantiles is None


# ---------------------------------------------------------------------------
# operator harvest: quantile block accuracy + telemetry accounting
# ---------------------------------------------------------------------------

def test_harvest_summary_quantiles_and_telemetry():
    rng = np.random.default_rng(1)
    n = 4000
    lat = _latencies(rng, n)
    lat[:250] = 0                      # no-magnitude events → zero bucket

    def counter(name) -> float:
        return sum(v for k, v in telemetry_registry.snapshot().items()
                   if k.startswith(name))

    ev0 = counter("ig_sketch_quantile_events_total")
    z0 = counter("ig_sketch_quantile_zero_total")
    inst = _make_instance({"quantiles": "true"})
    inst.enrich_batch(_batch(rng.integers(1, 1 << 32, n, dtype=np.uint64),
                             lat))
    s = inst.harvest()
    qt = s.quantiles
    assert qt is not None
    assert qt["total"] == n and qt["zeros"] == 250
    assert qt["alpha"] == 0.01
    pos = lat[lat > 0].astype(np.float64)
    for p, q in (("p50", 0.50), ("p90", 0.90), ("p99", 0.99)):
        true = float(np.quantile(lat.astype(np.float64), q))
        assert abs(qt[p] - true) / true < 0.03, (p, qt[p], true)
    assert pos.min() >= 1.0 and qt["underflow"] == 0
    # telemetry: every absorbed event counted once, zeros split out
    assert counter("ig_sketch_quantile_events_total") == ev0 + n
    assert counter("ig_sketch_quantile_zero_total") == z0 + 250
    # an empty plane-on harvest reads all-zero — never NaN on the wire
    empty = _make_instance({"quantiles": "true"})
    q2 = empty.harvest().quantiles
    assert q2 is not None
    assert q2["total"] == 0 and q2["p50"] == 0.0 and q2["p999"] == 0.0


def test_plane_off_summary_and_wire_shape():
    from inspektor_gadget_tpu.agent import wire
    from inspektor_gadget_tpu.operators.tpusketch import SketchSummary

    rng = np.random.default_rng(2)
    inst = _make_instance({})
    inst.enrich_batch(_batch(rng.integers(1, 1 << 32, 100, dtype=np.uint64),
                             _latencies(rng, 100)))
    s = inst.harvest()
    assert s.quantiles is None
    # plane-off summaries keep the pre-plane header shape exactly
    h, _ = wire.encode_summary(s)
    assert "quantiles" not in h
    # plane-on: the block roundtrips the wire verbatim
    qs = SketchSummary(
        events=10, drops=0, distinct=3.0, entropy_bits=1.5,
        heavy_hitters=[(1, 5)], epoch=2,
        quantiles={"p50": 1.0, "p90": 2.0, "p99": 3.0, "p999": 4.0,
                   "zeros": 1, "total": 10, "underflow": 0,
                   "alpha": 0.01})
    h2, payload = wire.encode_summary(qs)
    out = wire.decode_summary(h2, payload)
    assert out["quantiles"]["p99"] == 3.0
    assert out["quantiles"]["total"] == 10


# ---------------------------------------------------------------------------
# fleet: sealed-window deltas, merged accuracy, mixed-coverage refusal
# ---------------------------------------------------------------------------

def test_sealed_window_deltas_and_query_matches_live_read(fleet_store):
    rng = np.random.default_rng(3)
    n = 600
    lat = _latencies(rng, n)
    keys = rng.integers(1, 1 << 32, n, dtype=np.uint64)
    inst = _make_instance(
        {"quantiles": "true", "history": "true", "history-interval": "0",
         "history-log2-width": "8", "history-slots": "2"}, node="nA")
    inst.enrich_batch(_batch(keys[: n // 2], lat[: n // 2]))
    inst.seal_window()
    inst.enrich_batch(_batch(keys[n // 2:], lat[n // 2:]))
    inst.seal_window()
    live = inst.harvest().quantiles
    HISTORY.release(inst._hist_writer)
    frames = list(HISTORY.fetch_windows(base_dir=fleet_store, gadget=GADGET))
    wins = decode_frames(frames)
    assert len(wins) == 2
    # per-window DELTAS: each carries exactly its half of the stream
    assert sorted(w.qt_total for w in wins) == [n // 2, n // 2]
    ans = answer_query(wins)
    # dd_merge is lossless: the range fold reads EXACTLY like the live
    # bundle that produced the windows
    assert ans.quantiles == live
    assert ans.histogram is not None
    assert sum(ans.histogram) == n - live["zeros"]
    # the JSON surface carries both blocks
    doc = ans.to_dict()
    assert doc["quantiles"]["total"] == n
    assert doc["histogram"] == ans.histogram


def test_two_node_bimodal_merge_accuracy(fleet_store):
    """The acceptance shape: node nA is healthy, node nB regressed 10x.
    The merged fleet answer reads the TRUE combined distribution — a
    per-node average could never show the bimodal p99."""
    rng = np.random.default_rng(4)
    streams = {"nA": _latencies(rng, 500, median_ns=30_000.0),
               "nB": _latencies(rng, 500, median_ns=300_000.0)}
    for node, lat in streams.items():
        inst = _make_instance(
            {"quantiles": "true", "history": "true",
             "history-interval": "0", "history-log2-width": "8",
             "history-slots": "2"}, node=node)
        inst.enrich_batch(_batch(
            rng.integers(1, 1 << 32, len(lat), dtype=np.uint64), lat))
        inst.seal_window()
        HISTORY.release(inst._hist_writer)
    frames = list(HISTORY.fetch_windows(base_dir=fleet_store, gadget=GADGET))
    ans = answer_query(decode_frames(frames))
    both = np.concatenate(list(streams.values())).astype(np.float64)
    for p, q in (("p50", 0.50), ("p90", 0.90), ("p99", 0.99)):
        true = float(np.quantile(both, q))
        assert abs(ans.quantiles[p] - true) / true < 0.03, (p,)
    assert ans.quantiles["total"] == 1000


def test_mixed_coverage_drops_plane_loudly(fleet_store):
    """One node sealed without the plane: the merged range REFUSES to
    answer quantiles (partial coverage would lie) and says why."""
    rng = np.random.default_rng(5)
    for node, qt in (("nA", "true"), ("nB", "false")):
        inst = _make_instance(
            {"quantiles": qt, "history": "true", "history-interval": "0",
             "history-log2-width": "8", "history-slots": "2"}, node=node)
        inst.enrich_batch(_batch(
            rng.integers(1, 1 << 32, 200, dtype=np.uint64),
            _latencies(rng, 200)))
        inst.seal_window()
        HISTORY.release(inst._hist_writer)
    frames = list(HISTORY.fetch_windows(base_dir=fleet_store, gadget=GADGET))
    ans = answer_query(decode_frames(frames))
    assert ans.quantiles is None and ans.histogram is None
    assert any("quantile" in note for note in ans.dropped_windows)


# ---------------------------------------------------------------------------
# CLI: ig-tpu query --quantiles
# ---------------------------------------------------------------------------

def _seal_one(fleet_store, rng, node="nQ"):
    lat = _latencies(rng, 400)
    inst = _make_instance(
        {"quantiles": "true", "history": "true", "history-interval": "0",
         "history-log2-width": "8", "history-slots": "2"}, node=node)
    inst.enrich_batch(_batch(
        rng.integers(1, 1 << 32, 400, dtype=np.uint64), lat))
    inst.seal_window()
    HISTORY.release(inst._hist_writer)
    return lat


class _Args:
    remote = ""
    gadget = GADGET
    start_ts = None
    end_ts = None
    last = ""
    start_seq = None
    end_seq = None
    key = ""
    slices = False
    top = 20
    output = "table"
    quantiles = True

    def __init__(self, **kv):
        for k, v in kv.items():
            setattr(self, k, v)


def test_query_cli_quantiles_table_and_json(fleet_store, capsys):
    from inspektor_gadget_tpu.cli.query import cmd_query

    rng = np.random.default_rng(6)
    _seal_one(fleet_store, rng)
    assert cmd_query(_Args(history=fleet_store)) == 0
    out = capsys.readouterr().out
    assert "latency quantiles" in out
    assert "p99" in out and "ddsketch" in out
    # biolatency-style histogram rows render under the block
    assert "|" in out and "[" in out
    # the JSON surface carries the block verbatim
    assert cmd_query(_Args(history=fleet_store, output="json")) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["quantiles"]["total"] == 400
    assert isinstance(doc["histogram"], list)


def test_query_cli_quantiles_not_available(fleet_store, capsys):
    from inspektor_gadget_tpu.cli.query import cmd_query

    rng = np.random.default_rng(7)
    inst = _make_instance(
        {"history": "true", "history-interval": "0",
         "history-log2-width": "8", "history-slots": "2"}, node="nP")
    inst.enrich_batch(_batch(
        rng.integers(1, 1 << 32, 100, dtype=np.uint64)))
    inst.seal_window()
    HISTORY.release(inst._hist_writer)
    assert cmd_query(_Args(history=fleet_store)) == 0
    out = capsys.readouterr().out
    assert "quantiles: not available" in out


def test_render_histogram_log2_shape():
    from inspektor_gadget_tpu.cli.query import render_histogram_log2

    assert render_histogram_log2([0, 0, 0]) == []
    rows = render_histogram_log2([0, 4, 0, 2, 0])
    # contiguous lo..hi range, zero rows kept for visual continuity
    assert len(rows) == 3
    assert "[         2,          4)" in rows[0]
    assert rows[0].count("*") == 40        # peak row fills the bar
    assert rows[2].count("*") == 20


# ---------------------------------------------------------------------------
# sharded ingest: bit-identity at any chip count
# ---------------------------------------------------------------------------

def test_sharded_summary_quantiles_identical_to_single_chip():
    import jax
    if jax.local_device_count() < 4:
        pytest.skip("needs the 8-device CPU topology from conftest")
    rng = np.random.default_rng(8)
    n = 900
    keys = rng.integers(1, 1 << 32, n, dtype=np.uint64)
    lat = _latencies(rng, n)
    lat[:40] = 0
    ref = _make_instance({"quantiles": "true"})
    shard = _make_instance({"quantiles": "true", "shard-ingest": "true",
                            "chips": "4"})
    for i in range(3):
        ref.enrich_batch(_batch(keys[i::3], lat[i::3]))
        shard.enrich_batch(_batch(keys[i::3], lat[i::3]))
    s_ref, s_shard = ref.harvest(), shard.harvest()
    # the psum fold over int32 lanes is exact: identical, not just close
    assert s_ref.quantiles == s_shard.quantiles
    assert s_ref.quantiles["total"] == n
    assert s_ref.quantiles["zeros"] == 40
    shard.post_gadget_run()
    ref.post_gadget_run()


def test_quantile_plane_resume_from_checkpoint(tmp_path):
    from inspektor_gadget_tpu.operators import tpusketch

    tpusketch.set_checkpoint_dir(str(tmp_path))
    try:
        rng = np.random.default_rng(9)
        params = {"quantiles": "true"}
        keys = rng.integers(1, 1 << 32, 300, dtype=np.uint64)
        lat = _latencies(rng, 300)
        inst = _make_instance(params)
        inst.enrich_batch(_batch(keys, lat))
        inst.checkpoint()
        # "restart": a fresh instance resumes the DDSketch lanes with
        # the rest of the bundle, so totals span the restart
        inst2 = _make_instance(params)
        inst2.enrich_batch(_batch(keys, lat))
        qt = inst2.harvest().quantiles
        assert qt["total"] == 600
    finally:
        tpusketch.set_checkpoint_dir(None)


# ---------------------------------------------------------------------------
# alerts: the quantile_shift detector kind
# ---------------------------------------------------------------------------

def test_quantile_shift_rule_validation():
    from inspektor_gadget_tpu.alerts.rules import RuleError, load_rules

    rules = load_rules(json.dumps([{"id": "qs", "kind": "quantile_shift",
                                    "factor": 2.0}]))
    assert rules[0].field == "p99"          # the default percentile
    assert rules[0].threshold == 0.0        # threshold optional
    assert "quantile plane" in rules[0].describe()
    rules2 = load_rules(json.dumps([{"id": "qs", "kind": "quantile_shift",
                                     "field": "p50", "threshold": 500}]))
    assert rules2[0].field == "p50"
    with pytest.raises(RuleError, match="quantile_shift watches"):
        load_rules(json.dumps([{"id": "qs", "kind": "quantile_shift",
                                "field": "entropy"}]))


def test_quantile_shift_fires_once_on_regression():
    """Bimodal acceptance at the engine layer: healthy epochs build the
    baseline, an idle window (0.0 = no observation) must NOT poison it,
    the 3x regression epoch fires exactly once, and staying regressed
    does not re-fire."""
    from inspektor_gadget_tpu.alerts.engine import AlertEngine
    from inspektor_gadget_tpu.alerts.rules import load_rules

    rules = load_rules(json.dumps([{
        "id": "lat", "kind": "quantile_shift", "field": "p99",
        "factor": 2.0, "window": 3, "threshold": 1000, "for": 0}]))
    eng = AlertEngine(rules, node="n0", gadget=GADGET, dry_run=True)
    base = {"events": 100, "drops": 0, "distinct": 5.0, "entropy": 1.0,
            "heavy_hitters": [], "anomaly": {}}

    def obs(epoch, p99, now):
        return eng.observe({**base, "epoch": epoch,
                            "quantiles": {"p50": p99 / 2, "p90": p99 * 0.9,
                                          "p99": p99, "p999": p99 * 1.1}},
                           now=now)

    transitions = []
    # 3 healthy epochs (~100k ns), one idle window in the middle
    for i, p99 in enumerate((100_000.0, 101_000.0, 0.0, 99_000.0)):
        transitions += [(e.transition, i) for e in obs(i, p99, 10.0 * i)]
    assert transitions == []                # baseline warmup never fires
    # the regression epoch: 3x the baseline mean → exactly one firing
    evs = obs(4, 300_000.0, 40.0)
    # for: 0 → pending surfaces and promotes in the same epoch; exactly
    # ONE firing transition cluster-wide for the whole regression
    assert [e.transition for e in evs] == ["pending", "firing"]
    assert evs[-1].rule == "lat"
    assert evs[-1].value == 300_000.0
    # still regressed next epoch: the alert is already up — no re-fire
    evs2 = obs(5, 310_000.0, 50.0)
    assert not any(e.transition == "firing" for e in evs2)
    eng.close()


def test_quantile_shift_ignores_plane_off_summaries():
    """A fleet mixing plane-on and plane-off nodes: summaries without
    the block read 0.0 (= no observation) and can never trip the rule
    or drag the baseline toward zero."""
    from inspektor_gadget_tpu.alerts.engine import AlertEngine
    from inspektor_gadget_tpu.alerts.rules import load_rules

    rules = load_rules(json.dumps([{
        "id": "lat", "kind": "quantile_shift", "factor": 1.1,
        "window": 2, "for": 0}]))
    eng = AlertEngine(rules, node="n0", gadget=GADGET, dry_run=True)
    base = {"events": 100, "drops": 0, "distinct": 5.0, "entropy": 1.0,
            "heavy_hitters": [], "anomaly": {}}
    evs = []
    for epoch in range(6):                   # plane off: no quantiles key
        evs += eng.observe({**base, "epoch": epoch}, now=10.0 * epoch)
    assert evs == []
    eng.close()


# ---------------------------------------------------------------------------
# perf: micro-bench records + harness stages (tier-1 smoke)
# ---------------------------------------------------------------------------

def test_quantile_bench_publishes_schema_valid_records(tmp_path):
    from inspektor_gadget_tpu.perf.compare import compare_ledger
    from inspektor_gadget_tpu.perf.ledger import read_ledger
    from inspektor_gadget_tpu.perf.quantile_bench import publish
    from inspektor_gadget_tpu.perf.schema import validate_record

    ledger = str(tmp_path / "PERF.jsonl")
    records = publish(batch=1 << 10, n_buckets=256, seconds=0.05,
                      ledger=ledger)
    assert {r["config"] for r in records} == {"quantile-update",
                                              "quantile-merge"}
    for rec in records:
        assert validate_record(rec) == []
    on_disk = read_ledger(ledger).records
    assert len(on_disk) == 2
    # the series gates like any other: fresh series → no baseline → rc 0
    assert all(r.rc == 0 for r in compare_ledger(on_disk))
