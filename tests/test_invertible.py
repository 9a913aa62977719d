"""Invertible heavy-key recovery (ISSUE 15): operator, fleet, alerts,
perf surfaces.

The acceptance story under test: heavy-hitter recovery stops depending
on the host candidate ring. A 2-node fleet seals invertible-plane
windows per node; decoding the MERGED state recovers every ground-truth
key with its EXACT aggregate count — including a key that is heavy only
in aggregate and absent from BOTH nodes' candidate rings — while the
candidate-overflow satellite flags (approx=True + counter) exactly when
the ring stopped being exact, and PSketch-style priority classes keep a
hot tenant's decode complete when the whole stream overflows the base
geometry.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

import jax.numpy as jnp

import inspektor_gadget_tpu.all_gadgets  # noqa: F401
from inspektor_gadget_tpu.gadgets import GadgetContext, get
from inspektor_gadget_tpu.history import HISTORY, answer_query, decode_frames
from inspektor_gadget_tpu.operators.operators import get as get_op
from inspektor_gadget_tpu.ops import fold64_to_32
from inspektor_gadget_tpu.params import ParamError
from inspektor_gadget_tpu.sources.batch import EventBatch
from inspektor_gadget_tpu.telemetry import registry as telemetry_registry

GADGET = "trace/exec"
K_RING = 8


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
    p.set("topk", str(K_RING))
    p.set("harvest-interval", "1h")
    for k, v in extra_params.items():
        p.set(k, v)
    return op.instantiate(ctx, None, p)


def _batch(keys64: np.ndarray, mntns: np.ndarray | None = None
           ) -> EventBatch:
    b = EventBatch.alloc(len(keys64), with_comm=False)
    b.cols["key_hash"][:] = keys64
    if mntns is not None:
        b.cols["mntns"][:] = mntns
    b.count = len(keys64)
    return b


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

    # grammar errors answer at the params layer (set-time validator)
    for bad, match in (
        ("gibberish", "name=log2buckets"),
        ("a=12:1,a=10:*", "duplicate class name"),
        ("a=12:7,b=10:7|8,c=9:*", "already claimed"),
        ("a=12:7", "no '\\*' catch-all"),
        ("a=12:*,b=10:*", "second '\\*' catch-all"),
        ("a=99:*", "outside \\[6, 20\\]"),
        ("a=xx:*", "not an integer"),
        ("a=12:", "empty tenant"),
    ):
        with pytest.raises(ParamError, match=match):
            params(**{"priority-classes": bad})
    with pytest.raises(ParamError):
        params(**{"inv-log2-buckets": "25"})
    with pytest.raises(ParamError):
        params(**{"inv-rows": "1"})
    # classes without the plane: loud at instantiation
    with pytest.raises(ParamError, match="needs 'invertible true'"):
        _make_instance({"priority-classes": "hot=9:101,rest=8:*"})
    # budget overrun: classes must PARTITION the base geometry
    with pytest.raises(ParamError, match="budgets"):
        _make_instance({"invertible": "true", "inv-log2-buckets": "9",
                        "priority-classes": "hot=9:101,rest=9:*"})
    # fitting classes instantiate
    inst = _make_instance({"invertible": "true", "inv-log2-buckets": "10",
                           "priority-classes": "hot=9:101,rest=8:*"})
    assert inst.enabled and len(inst._inv_classes) == 2


# ---------------------------------------------------------------------------
# operator harvest: decode, ring-miss reporting, overflow accounting
# ---------------------------------------------------------------------------

def _two_tier_stream(rng, base: int):
    """12 per-node-heavy keys (counts 500..390) + one moderate key X
    (count 300): X sits outside a k=8 candidate ring on every node but
    dominates any single key once two nodes merge."""
    counts = {np.uint64(base + i): 500 - 10 * i for i in range(12)}
    x = np.uint64(9999)
    counts[x] = 300
    keys = np.repeat(np.array(list(counts), dtype=np.uint64),
                     list(counts.values()))
    rng.shuffle(keys)
    return keys, counts, x


def test_harvest_decodes_ring_missed_keys_and_flags_overflow():
    rng = np.random.default_rng(1)
    keys, counts, x = _two_tier_stream(rng, 1000)
    x32 = int(fold64_to_32(np.array([x]))[0])
    truth = {int(fold64_to_32(np.array([k]))[0]): c
             for k, c in counts.items()}
    inst = _make_instance({"invertible": "true", "inv-log2-buckets": "8"})

    def overflow_total() -> float:
        return sum(v for k, v in telemetry_registry.snapshot().items()
                   if k.startswith("ig_sketch_candidate_overflow_total"))

    before = overflow_total()
    inst.enrich_batch(_batch(keys))
    s = inst.harvest()
    # 13 distinct candidates > k=8: the ring saturated and says so
    assert s.approx is True
    assert overflow_total() == before + 1
    # a second harvest must not double-count the same run
    inst.harvest()
    assert overflow_total() == before + 1
    # decode recovers EVERY key exactly (13 distinct << capacity)
    assert dict(s.decoded) == truth
    assert s.inv["complete"] is True
    # the ring (k=8) missed X; decode reports exactly that
    ring = {k for k, _ in s.heavy_hitters}
    assert x32 not in ring
    assert (x32, 300) in s.decoded_only


def test_no_overflow_no_flag():
    inst = _make_instance({"invertible": "true", "inv-log2-buckets": "8"})
    keys = np.repeat(np.arange(1, K_RING + 1, dtype=np.uint64), 20)
    inst.enrich_batch(_batch(keys))
    s = inst.harvest()
    assert s.approx is False
    assert s.decoded_only == []


def test_priority_classes_protect_hot_tenant():
    """PSketch semantics: the flood tenant overloads its class (decode
    partial, reported), the hot tenant's class stays COMPLETE and exact
    under the same total memory budget."""
    rng = np.random.default_rng(2)
    hot_keys = rng.choice(np.arange(1, 1 << 20, dtype=np.uint64), 50,
                          replace=False)
    hot_truth = {int(fold64_to_32(np.array([k]))[0]): 4 for k in hot_keys}
    flood_keys = rng.choice(np.arange(1 << 20, 1 << 22, dtype=np.uint64),
                            3000, replace=False)
    keys = np.concatenate([np.repeat(hot_keys, 4), flood_keys])
    mntns = np.concatenate([np.full(200, 101, np.uint64),
                            np.full(3000, 202, np.uint64)])
    order = rng.permutation(len(keys))
    inst = _make_instance({
        "invertible": "true", "inv-log2-buckets": "10",
        "priority-classes": "hot=9:101,rest=8:*"})
    inst.enrich_batch(_batch(keys[order], mntns[order]))
    s = inst.harvest()
    assert s.classes is not None
    hot = s.classes["hot"]
    rest = s.classes["rest"]
    # hot tenant: 50 distinct << capacity(3, 2^9)=384 → complete + exact
    assert hot["complete"] is True
    assert dict(hot["decoded"]) == dict(
        sorted(hot_truth.items(), key=lambda kv: (-kv[1], kv[0]))[:32])
    assert hot["residual_events"] == 0
    # flood tenant: 3000 distinct >> capacity(3, 2^8)=192 → partial,
    # honestly reported — never wrong, just incomplete
    assert rest["complete"] is False
    assert rest["recovered"] < 3000


@pytest.mark.skipif("config.getoption('-m', default='') == 'slow'")
def test_sharded_summary_decoded_identical_to_single_chip():
    """The inv plane rides the lane-stacked bundle and the psum harvest:
    summaries (decoded keys included) are identical at any chip count —
    the PR-11 bit-identity contract extended to the new plane."""
    import jax
    if jax.local_device_count() < 4:
        pytest.skip("needs the 8-device CPU topology from conftest")
    rng = np.random.default_rng(3)
    keys, _counts, _x = _two_tier_stream(rng, 3000)
    batches = [keys[i::3] for i in range(3)]
    ref = _make_instance({"invertible": "true", "inv-log2-buckets": "8"})
    shard = _make_instance({"invertible": "true", "inv-log2-buckets": "8",
                            "shard-ingest": "true", "chips": "4"})
    for b in batches:
        ref.enrich_batch(_batch(b))
        shard.enrich_batch(_batch(b))
    s_ref, s_shard = ref.harvest(), shard.harvest()
    assert s_ref.decoded == s_shard.decoded
    assert s_ref.decoded_only == s_shard.decoded_only
    assert s_ref.heavy_hitters == s_shard.heavy_hitters
    assert s_ref.approx == s_shard.approx
    shard.post_gadget_run()
    ref.post_gadget_run()


def test_priority_classes_resume_from_checkpoint(tmp_path):
    """Class sketches checkpoint/resume like the bundle: after a
    restart, per-class decodes still reproduce whole-stream totals
    (the class_weights invariant) instead of silently under-reporting
    the pre-restart half."""
    from inspektor_gadget_tpu.operators import tpusketch

    tpusketch.set_checkpoint_dir(str(tmp_path))
    try:
        params = {"invertible": "true", "inv-log2-buckets": "10",
                  "priority-classes": "hot=9:101,rest=8:*"}
        keys = np.repeat(np.arange(1, 21, dtype=np.uint64), 15)
        mntns = np.full(len(keys), 101, np.uint64)
        inst = _make_instance(params)
        inst.enrich_batch(_batch(keys, mntns))
        inst.checkpoint()
        # "restart": a fresh instance resumes bundle AND class state
        inst2 = _make_instance(params)
        inst2.enrich_batch(_batch(keys, mntns))
        s = inst2.harvest()
        truth = {int(fold64_to_32(np.array([np.uint64(k)]))[0]): 30
                 for k in range(1, 21)}
        assert dict(s.decoded) == truth          # whole-stream: 2×15
        assert dict(s.classes["hot"]["decoded"]) == truth  # class matches
        assert s.classes["hot"]["complete"] is True
    finally:
        tpusketch.set_checkpoint_dir(None)


# ---------------------------------------------------------------------------
# acceptance: 2-node fleet — decode of MERGED windows recovers the
# aggregate-heavy key both candidate rings missed
# ---------------------------------------------------------------------------

@pytest.fixture()
def fleet_store(tmp_path):
    HISTORY.set_base_dir(str(tmp_path))
    yield str(tmp_path)
    HISTORY.close_all()
    HISTORY.set_base_dir(None)


def test_two_node_merged_decode_recovers_aggregate_heavy_key(fleet_store):
    rng = np.random.default_rng(4)
    truth_total: dict[int, int] = {}
    x32 = int(fold64_to_32(np.array([np.uint64(9999)]))[0])
    for node, base in (("nA", 1000), ("nB", 2000)):
        keys, counts, _x = _two_tier_stream(rng, base)
        # a zipf tail per node (keys shared across nodes) on top of the
        # two-tier head: the acceptance stream shape from the issue
        tail_keys = rng.choice(np.arange(50_000, 50_120, dtype=np.uint64),
                               60, replace=False)
        tail_counts = rng.zipf(1.5, 60).clip(1, 99).astype(np.int64)
        for k, c in zip(tail_keys.tolist(), tail_counts.tolist()):
            counts[np.uint64(k)] = counts.get(np.uint64(k), 0) + int(c)
        keys = np.concatenate([keys, np.repeat(tail_keys, tail_counts)])
        rng.shuffle(keys)
        for k, c in counts.items():
            k32 = int(fold64_to_32(np.array([k]))[0])
            truth_total[k32] = truth_total.get(k32, 0) + c
        inst = _make_instance(
            {"invertible": "true", "inv-log2-buckets": "9",
             "history": "true", "history-interval": "0",
             "history-log2-width": "8", "history-slots": "2"},
            node=node)
        # two batches per node → window deltas must re-merge exactly
        inst.enrich_batch(_batch(keys[: len(keys) // 2]))
        inst.seal_window()
        inst.enrich_batch(_batch(keys[len(keys) // 2:]))
        inst.seal_window()
        HISTORY.release(inst._hist_writer)
    frames = list(HISTORY.fetch_windows(base_dir=fleet_store,
                                        gadget=GADGET))
    assert len(frames) == 4  # 2 nodes × 2 windows
    ans = answer_query(decode_frames(frames), top=512)
    # every ground-truth key above the documented threshold (here: all
    # 25 keys — the load is far under capacity) decodes with its EXACT
    # aggregate count
    got = {k: c for k, c, _label in ans.heavy_flows}
    assert got == truth_total
    assert ans.inv["complete"] is True
    # X (300 per node, outside both k=8 rings) is the TOP aggregate key
    # — and the candidate path never saw it
    assert ans.heavy_flows[0][0] == x32
    assert ans.heavy_flows[0][1] == 600
    ring = {k for k, _c, _label in ans.heavy_hitters}
    assert x32 not in ring
    assert x32 in {k for k, _c, _label in ans.decoded_only}
    # JSON surface (satellite 2): the decoded-only field rides to_dict
    doc = ans.to_dict()
    assert any(row["count"] == 600 for row in doc["heavy_flows"])
    assert any(row["key"] == f"0x{x32:08x}" for row in doc["decoded_only"])


def test_query_cli_reports_heavy_flows_json(fleet_store, capsys):
    rng = np.random.default_rng(5)
    keys, counts, _x = _two_tier_stream(rng, 4000)
    inst = _make_instance(
        {"invertible": "true", "inv-log2-buckets": "8",
         "history": "true", "history-interval": "0",
         "history-log2-width": "8", "history-slots": "2"}, node="nQ")
    inst.enrich_batch(_batch(keys))
    inst.seal_window()
    HISTORY.release(inst._hist_writer)

    from inspektor_gadget_tpu.cli.query import cmd_query

    class _Args:
        remote = ""
        history = fleet_store
        gadget = GADGET
        start_ts = None
        end_ts = None
        last = ""
        start_seq = None
        end_seq = None
        key = ""
        slices = False
        top = 20
        output = "json"

    assert cmd_query(_Args()) == 0
    doc = json.loads(capsys.readouterr().out)
    x32 = int(fold64_to_32(np.array([np.uint64(9999)]))[0])
    flows = {int(r["key"], 16): r["count"] for r in doc["heavy_flows"]}
    assert flows[x32] == 300
    assert doc["inv"]["complete"] is True
    assert any(int(r["key"], 16) == x32 for r in doc["decoded_only"])


# ---------------------------------------------------------------------------
# alerts: the heavy_flow detector kind
# ---------------------------------------------------------------------------

def test_heavy_flow_rule_validation():
    from inspektor_gadget_tpu.alerts.rules import RuleError, load_rules

    rules = load_rules(json.dumps([{"id": "hf", "kind": "heavy_flow",
                                    "threshold": 100}]))
    assert rules[0].kind == "heavy_flow"
    assert "invertible" in rules[0].describe()
    with pytest.raises(RuleError, match="missing 'threshold'"):
        load_rules(json.dumps([{"id": "hf", "kind": "heavy_flow"}]))
    with pytest.raises(RuleError, match="remove field"):
        load_rules(json.dumps([{"id": "hf", "kind": "heavy_flow",
                                "threshold": 1, "field": "events"}]))


def test_heavy_flow_rule_fires_per_decoded_key_and_resolves():
    from inspektor_gadget_tpu.alerts.engine import AlertEngine
    from inspektor_gadget_tpu.alerts.rules import load_rules

    rules = load_rules(json.dumps([{"id": "hf", "kind": "heavy_flow",
                                    "threshold": 100, "severity":
                                    "critical"}]))
    eng = AlertEngine(rules, node="n0", gadget=GADGET, dry_run=True)
    base = {"events": 1000, "drops": 0, "distinct": 10.0, "entropy": 1.0,
            "epoch": 1, "heavy_hitters": [], "anomaly": {}}
    evs = eng.observe({**base, "decoded": [[0xAB, 500], [0xCD, 50]]},
                      now=10.0)
    fired = {(e.key, e.transition) for e in evs}
    assert ("key:0x000000ab", "firing") in fired          # exact + above
    assert not any(k == "key:0x000000cd" for k, _t in fired)  # below
    # the key stops decoding → vanished-key sweep resolves it
    evs2 = eng.observe({**base, "epoch": 2, "decoded": []}, now=20.0)
    assert {(e.key, e.transition) for e in evs2} == {
        ("key:0x000000ab", "resolved")}


def test_summary_wire_roundtrip_carries_inv_fields():
    from inspektor_gadget_tpu.agent import wire
    from inspektor_gadget_tpu.operators.tpusketch import SketchSummary

    s = SketchSummary(
        events=10, drops=0, distinct=3.0, entropy_bits=1.5,
        heavy_hitters=[(1, 5)], epoch=2, approx=True,
        decoded=[(1, 5), (7, 3)], decoded_only=[(7, 3)],
        inv={"recovered": 2, "complete": True, "residual_events": 0,
             "capacity": 768},
        classes={"hot": {"complete": True, "decoded": [[1, 5]]}})
    h, payload = wire.encode_summary(s)
    out = wire.decode_summary(h, payload)
    assert out["approx"] is True
    assert out["decoded"] == [[1, 5], [7, 3]]
    assert out["decoded_only"] == [[7, 3]]
    assert out["inv"]["complete"] is True
    assert out["classes"]["hot"]["decoded"] == [[1, 5]]
    # plane-off summaries keep the pre-plane header shape exactly
    plain = SketchSummary(events=1, drops=0, distinct=1.0,
                          entropy_bits=0.0, heavy_hitters=[])
    h2, _ = wire.encode_summary(plain)
    assert not ({"approx", "decoded", "decoded_only", "inv", "classes"}
                & set(h2))


# ---------------------------------------------------------------------------
# perf: micro-bench records (tier-1 smoke)
# ---------------------------------------------------------------------------

def test_invertible_bench_publishes_schema_valid_records(tmp_path):
    from inspektor_gadget_tpu.perf.invertible_bench import publish
    from inspektor_gadget_tpu.perf.ledger import read_ledger
    from inspektor_gadget_tpu.perf.schema import validate_record

    ledger = str(tmp_path / "PERF.jsonl")
    records = publish(batch=1 << 10, n_keys=128, rows=2, log2_buckets=9,
                      seconds=0.05, ledger=ledger)
    assert {r["config"] for r in records} == {"inv-update", "inv-decode"}
    for rec in records:
        assert validate_record(rec) == []
    assert records[1]["extra"]["complete"] == 1.0
    on_disk = read_ledger(ledger).records
    assert len(on_disk) == 2
    # the series gates like any other: fresh series → no baseline → rc 0
    from inspektor_gadget_tpu.perf.compare import compare_ledger
    results = compare_ledger(on_disk)
    assert all(r.rc == 0 for r in results)
