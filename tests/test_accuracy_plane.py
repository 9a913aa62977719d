"""Accuracy audit plane (ISSUE 19): error envelopes, the shadow sample,
fleet surfaces, alerting, and the overflow-taint bugfix.

The acceptance story under test: every answer the fleet serves carries
its analytic error envelope for free, and a run with `audit-sample N`
additionally carries OBSERVED error against a deterministic bottom-k
shadow sample whose resident weights are exact ground truth. The sample
merges bit-identically under any fold order (windows, nodes, standing
queries); sealed wire bytes and digests with the plane off stay exactly
as they were before the plane existed; `accuracy_drift` turns an
estimate escaping its envelope into exactly one alert; and the TopK
candidate-overflow flag finally survives the seal boundary as
approx=True on every downstream answer.
"""

from __future__ import annotations

import json
import math
import tempfile
from pathlib import Path

import numpy as np
import pytest

import inspektor_gadget_tpu.all_gadgets  # noqa: F401
from inspektor_gadget_tpu.gadgets import GadgetContext, get
from inspektor_gadget_tpu.history import HISTORY, answer_query, decode_frames
from inspektor_gadget_tpu.operators.operators import get as get_op
from inspektor_gadget_tpu.ops.accuracy import (
    HLL_STDERR_CONST,
    LINEAR_COUNTING_FACTOR,
    AccuracyStats,
    ShadowSample,
    accuracy_block,
    accuracy_ratio,
    cms_bound,
    dd_bound,
    entropy_bias_bound,
    hll_bound,
)
from inspektor_gadget_tpu.sources.batch import EventBatch
from inspektor_gadget_tpu.telemetry import registry as telemetry_registry

GADGET = "trace/exec"
ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def _release_instances():
    """Instances built outside a real gadget run never see
    post_gadget_run — drop them from the live table (checkpoint_all
    iterates it), drain their stagers, and unregister their stats rows
    (including the accuracy plane's) so no state leaks across files."""
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
        if getattr(inst, "_astats", None) is not None:
            inst._astats.unregister()


@pytest.fixture()
def fleet_store(tmp_path):
    HISTORY.set_base_dir(str(tmp_path))
    yield str(tmp_path)
    HISTORY.close_all()
    HISTORY.set_base_dir(None)


def _make_instance(extra_params: dict, node: str = ""):
    desc = get("trace", "exec")
    ctx = GadgetContext(desc, extra={})
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


def _batch(keys64: np.ndarray) -> EventBatch:
    b = EventBatch.alloc(len(keys64), with_comm=False)
    b.cols["key_hash"][:] = keys64
    b.count = len(keys64)
    return b


def _zipf_stream(rng, n, vocab, s=1.3):
    """Skewed uint32 key stream over a small vocabulary (host-side, for
    direct ShadowSample property tests)."""
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    p = ranks ** -s
    p /= p.sum()
    ids = rng.choice(vocab, size=n, p=p)
    keys = rng.integers(1, 1 << 32, vocab, dtype=np.uint64).astype(np.uint32)
    return keys[ids]


# ---------------------------------------------------------------------------
# analytic envelopes: formulas + the docs drift-test
# ---------------------------------------------------------------------------

def test_analytic_bounds_formulas():
    hh = cms_bound(4, 65536, 1e6)
    assert hh["bound"] == pytest.approx(math.e / 65536)
    assert hh["bound_abs"] == pytest.approx(1e6 * math.e / 65536)
    assert hh["confidence"] == pytest.approx(1.0 - math.exp(-4))
    # HLL: ±1.04/√m, linear-counting regime labeled below 2.5·m
    d = hll_bound(8, estimate=100.0)
    assert d["bound"] == pytest.approx(HLL_STDERR_CONST / 16.0)
    assert d["regime"] == "linear_counting"          # 100 ≤ 2.5·256
    assert hll_bound(8, estimate=10_000.0)["regime"] == "raw"
    assert hll_bound(8)["regime"] == "raw"           # no estimate yet
    assert hll_bound(8, estimate=LINEAR_COUNTING_FACTOR * 256)[
        "regime"] == "linear_counting"               # switchover inclusive
    # DDSketch: the α guarantee is the parameter itself
    assert dd_bound(0.02)["bound"] == 0.02
    # entropy: (d − 1)/(2·w·ln 2) bits, floor at d = 1
    e = entropy_bias_bound(6, 100.0)
    assert e["bound"] == pytest.approx(99.0 / (2 * 64 * math.log(2)))
    assert entropy_bias_bound(6, 1.0)["bound"] == 0.0


def test_documented_formulas_match_code_constants():
    """Satellite (d): docs/observability.md states the envelopes with
    the CODE's constants interpolated — bumping HLL_STDERR_CONST or
    LINEAR_COUNTING_FACTOR without re-documenting fails here."""
    text = (ROOT / "docs" / "observability.md").read_text()
    assert f"{HLL_STDERR_CONST:g}/√m" in text
    assert f"{LINEAR_COUNTING_FACTOR:g}·m" in text
    assert "N·e/w" in text
    assert "1 − e^−d" in text
    assert "(d − 1)/(2·w·ln 2)" in text


# ---------------------------------------------------------------------------
# shadow sample: determinism, mergeability, exactness (the tentpole's
# property tests)
# ---------------------------------------------------------------------------

def test_shadow_sample_fold_orders_bit_identical():
    """merge = weighted subsample union over a fixed hash: single-pass,
    chunked incremental (any chunk order), left fold of per-chunk
    samples, and pairwise tree merge all yield the BIT-identical
    canonical state."""
    rng = np.random.default_rng(19)
    keys = _zipf_stream(rng, 20_000, 3000)
    cap = 256
    ref = ShadowSample(cap)
    ref.update(keys)

    chunks = np.array_split(keys, 13)
    for perm_seed in (0, 1, 2):
        order = np.random.default_rng(perm_seed).permutation(len(chunks))
        # incremental updates in permuted chunk order
        inc = ShadowSample(cap)
        for i in order:
            inc.update(chunks[i])
        assert np.array_equal(inc.keys, ref.keys)
        assert np.array_equal(inc.weights, ref.weights)
        # pairwise merges of per-chunk samples, same permuted order
        parts = []
        for i in order:
            s = ShadowSample(cap)
            s.update(chunks[i])
            parts.append(s)
        while len(parts) > 1:                      # tree fold
            parts = [parts[j].merge(parts[j + 1]) if j + 1 < len(parts)
                     else parts[j] for j in range(0, len(parts), 2)]
        assert np.array_equal(parts[0].keys, ref.keys)
        assert np.array_equal(parts[0].weights, ref.weights)
    assert ref.keys.dtype == np.uint32 and ref.weights.dtype == np.int64
    assert len(ref) == cap


def test_shadow_sample_resident_weights_are_exact_ground_truth():
    """The threshold argument: a key surviving the final bottom-k was
    never evicted, so its weight equals the true stream total — the
    property that makes the sample usable as ground truth (zipf
    unbiasedness satellite)."""
    rng = np.random.default_rng(7)
    keys = _zipf_stream(rng, 50_000, 2000)
    sh = ShadowSample(128)
    # feed in chunks (evictions happen mid-stream)
    for c in np.array_split(keys, 17):
        sh.update(c)
    uk, uc = np.unique(keys, return_counts=True)
    truth = dict(zip(uk.tolist(), uc.tolist()))
    assert len(sh) == 128 and sh.full
    for k, w in zip(sh.keys.tolist(), sh.weights.tolist()):
        assert w == truth[k], (k, w, truth[k])
    # the bottom-k estimators read the stream, not the sample
    true_distinct = float(uk.size)
    assert abs(sh.distinct_estimate() - true_distinct) / true_distinct < 0.35
    # observed_hh_err over resident keys with exact counts reads 0
    err, n_aud = sh.observed_hh_err(sh.keys[:16],
                                    sh.weights[:16].astype(np.float64),
                                    float(keys.size))
    assert err == 0.0 and n_aud == 16


def test_shadow_sample_entropy_estimator_regimes():
    """Entropy ground truth: EXACT while the sample never filled
    (nothing evicted → the plug-in entropy of the true multiset), and
    within fractions of a bit on a full sample over a balanced stream
    (the inverse-probability estimator's low-variance regime)."""
    rng = np.random.default_rng(13)
    vocab_keys = rng.integers(1, 1 << 32, 2000, dtype=np.uint64).astype(
        np.uint32)
    # not full: exact to machine precision
    small = vocab_keys[:100][rng.integers(0, 100, 5000)]
    sh = ShadowSample(256)
    sh.update(small)
    uk, uc = np.unique(small, return_counts=True)
    p = uc / uc.sum()
    true_h = float(-(p * np.log2(p)).sum())
    assert not sh.full
    assert sh.entropy_estimate(5000.0) == pytest.approx(true_h)
    # full over a balanced stream: every weight is comparable, so the
    # 1/τ scaling has low variance
    stream = vocab_keys[rng.integers(0, 2000, 50_000)]
    full = ShadowSample(128)
    for c in np.array_split(stream, 17):
        full.update(c)
    uk2, uc2 = np.unique(stream, return_counts=True)
    p2 = uc2 / uc2.sum()
    true_h2 = float(-(p2 * np.log2(p2)).sum())
    assert full.full
    assert abs(full.entropy_estimate(50_000.0) - true_h2) < 0.7


def test_shadow_sample_empty_and_off_noops():
    off = ShadowSample(0)
    off.update(np.arange(10, dtype=np.uint32))
    assert len(off) == 0                      # capacity 0: plane off
    s = ShadowSample(8)
    s.update(np.zeros(0, dtype=np.uint32))
    assert len(s) == 0                        # empty batch: no-op
    s.update(np.arange(1, 5, dtype=np.uint32))
    before_k, before_w = s.keys.copy(), s.weights.copy()
    merged = s.merge(ShadowSample(8))         # empty merge: identity
    assert np.array_equal(merged.keys, before_k)
    assert np.array_equal(merged.weights, before_w)
    with pytest.raises(ValueError, match="capacity mismatch"):
        s.merge(ShadowSample(16))
    s.reset()
    assert len(s) == 0 and s.distinct_estimate() == 0.0


def test_accuracy_block_and_ratio_shapes():
    rng = np.random.default_rng(3)
    keys = _zipf_stream(rng, 5_000, 60)
    sh = ShadowSample(256)
    sh.update(keys)
    uk, uc = np.unique(keys, return_counts=True)
    top = np.argsort(uc)[::-1][:8]
    blk = accuracy_block(
        events=float(keys.size), depth=3, width=1024, hll_p=8,
        ent_log2_width=6, distinct=float(uk.size),
        entropy_bits=2.0, hh_keys=uk[top],
        hh_counts=uc[top].astype(np.int64), qt_alpha=0.01, shadow=sh)
    assert blk["audited"] is True
    assert blk["sample_size"] == uk.size and blk["sample_capacity"] == 256
    hh = blk["stats"]["heavy_hitters"]
    assert hh["audited"] and hh["observed_err"] == 0.0   # exact counts fed
    assert hh["audited_keys"] == 8
    assert blk["stats"]["distinct"]["audited"]
    assert blk["stats"]["distinct"]["observed_err"] == 0.0  # truth == truth
    assert blk["stats"]["entropy"]["audited"]
    # the value lane has no shadow: quantiles stay analytic-only
    qt = blk["stats"]["quantiles"]
    assert qt == {"bound": 0.01, "observed_err": None, "audited": False}
    assert blk["ratio"] == accuracy_ratio(blk)
    # unaudited: bounds ride, observations don't, ratio reads 0 (idle
    # immunity — "no observation" is not "zero error")
    off = accuracy_block(events=1000.0, depth=3, width=1024, hll_p=8,
                         ent_log2_width=6, distinct=50.0, shadow=None)
    assert off["audited"] is False and off["ratio"] == 0.0
    assert off["stats"]["heavy_hitters"]["bound"] > 0
    assert all(not s["audited"] for s in off["stats"].values())
    assert accuracy_ratio(None) == 0.0


# ---------------------------------------------------------------------------
# operator harvest: the accuracy block + telemetry accounting
# ---------------------------------------------------------------------------

def _metric(name: str) -> float:
    return sum(v for k, v in telemetry_registry.snapshot().items()
               if k.startswith(name))


def test_harvest_summary_accuracy_and_telemetry():
    rng = np.random.default_rng(11)
    n = 3000
    keys = rng.integers(1, 1 << 32, 50, dtype=np.uint64)[
        rng.integers(0, 50, n)]
    fed0 = _metric("ig_sketch_audit_samples_total")
    inst = _make_instance({"audit-sample": "256"})
    inst.enrich_batch(_batch(keys))
    s = inst.harvest()
    acc = s.accuracy
    assert acc is not None and acc["audited"] is True
    assert 0 < acc["sample_size"] <= 50       # never filled: exact truth
    assert acc["sample_capacity"] == 256
    hh = acc["stats"]["heavy_hitters"]
    assert hh["audited"] and hh["observed_err"] is not None
    assert hh["bound"] == pytest.approx(math.e / 1024)
    assert acc["stats"]["distinct"]["audited"]
    assert acc["stats"]["entropy"]["audited"]
    assert "quantiles" not in acc["stats"]    # value lane off
    assert acc["ratio"] >= 0.0
    # every event fed the shadow exactly once, batch-grain
    assert _metric("ig_sketch_audit_samples_total") == fed0 + n
    assert _metric("ig_sketch_accuracy_ratio") == acc["ratio"]
    # the live row DumpState/doctor/fleet read
    snap = inst._astats.snapshot()
    assert snap["audited"] and snap["samples_fed"] == n
    assert snap["ratio"] == acc["ratio"]
    assert set(snap["stats"]) == {"heavy_hitters", "distinct", "entropy"}


def test_plane_off_summary_wire_and_digest_unchanged():
    """The FREE proof: a plane-off run has accuracy=None, no `accuracy`
    wire header, and the block can never perturb a summary digest —
    sealed history and `replay --verify` stay byte-identical."""
    from inspektor_gadget_tpu.agent import wire
    from inspektor_gadget_tpu.capture.journal import summary_digest
    from inspektor_gadget_tpu.operators.tpusketch import SketchSummary

    rng = np.random.default_rng(2)
    inst = _make_instance({})
    inst.enrich_batch(_batch(rng.integers(1, 1 << 32, 100,
                                          dtype=np.uint64)))
    s = inst.harvest()
    assert s.accuracy is None
    h, _ = wire.encode_summary(s)
    assert "accuracy" not in h
    # plane-on: the block roundtrips the wire verbatim
    blk = {"stats": {"heavy_hitters": {"bound": 0.0026, "bound_abs": 2.6,
                                       "confidence": 0.95,
                                       "observed_err": 0.0001,
                                       "audited": True, "audited_keys": 4}},
           "audited": True, "sample_size": 40, "sample_capacity": 256,
           "ratio": 0.04}
    on = SketchSummary(events=10, drops=0, distinct=3.0, entropy_bits=1.5,
                       heavy_hitters=[(1, 5)], epoch=2, accuracy=blk)
    h2, payload = wire.encode_summary(on)
    assert wire.decode_summary(h2, payload)["accuracy"] == blk
    # digest whitelist: the block cannot enter
    base = {"events": 100, "drops": 2, "distinct": 7.0, "entropy": 1.5,
            "epoch": 3, "heavy_hitters": [[1, 5], [2, 3]]}
    assert summary_digest(base) == summary_digest(dict(base, accuracy=blk))


# ---------------------------------------------------------------------------
# fleet history: per-window shadow deltas, merged audits, coverage rules
# ---------------------------------------------------------------------------

_HIST = {"history": "true", "history-interval": "0",
         "history-log2-width": "8", "history-slots": "4"}


def _seal_node(rng, node, keys64, extra=None):
    inst = _make_instance({**_HIST, **(extra or {})}, node=node)
    inst.enrich_batch(_batch(keys64))
    inst.seal_window()
    HISTORY.release(inst._hist_writer)
    return inst


def test_sealed_windows_carry_shadow_deltas_and_audited_answers(
        fleet_store):
    rng = np.random.default_rng(23)
    for node, lo in (("nA", 1), ("nB", 1 << 20)):
        # 60-key vocabulary per node: the 256-slot window shadow never
        # fills, so the sealed delta is the exact per-window multiset
        keys = rng.integers(lo, lo + 60, 500, dtype=np.uint64)
        # topk 64 > 60 live keys: the candidate ring stays exact, so
        # this is the clean (approx=False) path
        _seal_node(rng, node, keys, {"audit-sample": "256",
                                     "topk": "64"})
    frames = list(HISTORY.fetch_windows(base_dir=fleet_store,
                                        gadget=GADGET))
    wins = decode_frames(frames)
    assert len(wins) == 2
    for w in wins:
        assert w.rs_keys is not None and w.rs_capacity == 256
        assert w.rs_keys.dtype == np.uint32
        assert w.rs_weights.dtype == np.int64
        assert int(w.rs_weights.sum()) == 500     # exact per-window delta
    ans = answer_query(wins)
    acc = ans.accuracy
    assert acc is not None and acc["audited"] is True
    assert acc["stats"]["heavy_hitters"]["audited"]
    assert acc["stats"]["heavy_hitters"]["observed_err"] is not None
    assert ans.approx is False
    doc = ans.to_dict()
    assert doc["accuracy"]["audited"] is True and doc["approx"] is False


def test_plane_off_windows_unchanged_and_analytic_only(fleet_store):
    rng = np.random.default_rng(29)
    # 6 live keys: no candidate overflow either, so the header carries
    # neither accuracy-plane field
    _seal_node(rng, "nP", rng.integers(1, 7, 300, dtype=np.uint64))
    frames = list(HISTORY.fetch_windows(base_dir=fleet_store,
                                        gadget=GADGET))
    for h, payload in frames:
        # plane-off wire bytes byte-identical to the pre-plane format
        assert "rs_capacity" not in h and "approx" not in h
        assert b"rs_keys" not in payload
    ans = answer_query(decode_frames(frames))
    acc = ans.accuracy
    assert acc is not None                     # analytic bounds always ride
    assert acc["audited"] is False and acc["sample_size"] == 0
    assert acc["stats"]["heavy_hitters"]["bound"] > 0
    assert acc["stats"]["heavy_hitters"]["observed_err"] is None


def test_mixed_audit_coverage_drops_observed_error_loudly(fleet_store):
    """One node sealed without the shadow: the merged range keeps the
    analytic envelopes but REFUSES the observed-error audit (partial
    ground truth would lie) and says why."""
    rng = np.random.default_rng(31)
    _seal_node(rng, "nA", rng.integers(1, 4000, 300, dtype=np.uint64),
               {"audit-sample": "128"})
    _seal_node(rng, "nB", rng.integers(1, 4000, 300, dtype=np.uint64))
    frames = list(HISTORY.fetch_windows(base_dir=fleet_store,
                                        gadget=GADGET))
    ans = answer_query(decode_frames(frames))
    assert ans.accuracy is not None
    assert ans.accuracy["audited"] is False
    assert any("ground truth" in note for note in ans.dropped_windows)


# ---------------------------------------------------------------------------
# the satellite bugfix: candidate overflow crosses the seal boundary
# ---------------------------------------------------------------------------

def test_topk_overflow_taints_sealed_and_merged_answers(fleet_store):
    rng = np.random.default_rng(37)
    # 40 distinct live keys vs an 8-slot candidate ring: overflow latches
    hot = np.repeat(rng.integers(1, 1 << 32, 40, dtype=np.uint64), 20)
    _seal_node(rng, "nOv", rng.permutation(hot))
    # a clean node: 6 distinct keys never overflow the ring
    few = np.repeat(rng.integers(1, 1 << 32, 6, dtype=np.uint64), 50)
    _seal_node(rng, "nOk", few)
    frames = list(HISTORY.fetch_windows(base_dir=fleet_store,
                                        gadget=GADGET))
    wins = decode_frames(frames)
    by_node = {w.node: w for w in wins}
    assert by_node["nOv"].approx is True      # the latch crossed the seal
    assert by_node["nOk"].approx is False
    # one tainted window taints the merged answer, however many clean
    # windows join it
    ans = answer_query(wins)
    assert ans.approx is True
    assert ans.to_dict()["approx"] is True
    clean = answer_query([by_node["nOk"]])
    assert clean.approx is False


def test_query_cli_prints_error_bars_and_approx_note(fleet_store, capsys):
    from inspektor_gadget_tpu.cli.query import cmd_query

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
        quantiles = False

        def __init__(self, **kv):
            for k, v in kv.items():
                setattr(self, k, v)

    rng = np.random.default_rng(41)
    hot = np.repeat(rng.integers(1, 1 << 32, 40, dtype=np.uint64), 20)
    _seal_node(rng, "nQ", rng.permutation(hot), {"audit-sample": "128"})
    assert cmd_query(_Args(history=fleet_store)) == 0
    out = capsys.readouterr().out
    assert "overestimate ≤" in out            # CMS envelope on the header
    assert "±" in out                         # HLL bound on distinct
    assert "accuracy audit" in out            # shadow-sample audit table
    assert "approximate" in out               # the overflow note
    # JSON carries the block + taint verbatim
    assert cmd_query(_Args(history=fleet_store, output="json")) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["approx"] is True
    assert doc["accuracy"]["audited"] is True


# ---------------------------------------------------------------------------
# standing queries inherit the plane through the window monoid
# ---------------------------------------------------------------------------

def test_standing_query_fold_carries_audit_and_taint(fleet_store):
    from inspektor_gadget_tpu.queries.engine import SlidingFold

    rng = np.random.default_rng(43)
    hot = np.repeat(rng.integers(1, 1 << 32, 40, dtype=np.uint64), 20)
    _seal_node(rng, "nS1", rng.integers(1, 7, 400, dtype=np.uint64),
               {"audit-sample": "128"})          # 6 live keys: clean
    _seal_node(rng, "nS2", rng.permutation(hot), {"audit-sample": "128"})
    wins = decode_frames(list(HISTORY.fetch_windows(
        base_dir=fleet_store, gadget=GADGET)))
    wins.sort(key=lambda w: w.node)
    fold = SlidingFold(gadget=GADGET, node="standing")
    fold.push(wins[0])                        # clean, audited
    val = fold.value()
    assert val.rs_keys is not None and val.approx is False
    fold.push(wins[1])                        # overflowed, audited
    val2 = fold.value()
    assert val2.approx is True                # taint survives the fold
    ans = answer_query([val2])
    assert ans.approx is True
    assert ans.accuracy is not None and ans.accuracy["audited"] is True


# ---------------------------------------------------------------------------
# alerts: the accuracy_drift detector kind
# ---------------------------------------------------------------------------

def test_accuracy_drift_rule_validation():
    from inspektor_gadget_tpu.alerts.rules import RuleError, load_rules

    rules = load_rules(json.dumps([{"id": "ad", "kind": "accuracy_drift",
                                    "factor": 0.5}]))
    assert rules[0].field == "accuracy_ratio"   # implied, not chosen
    assert rules[0].threshold == 0.0            # threshold optional
    assert "analytic bound" in rules[0].describe()
    # restating the implied field exactly is fine; any other is loud
    load_rules(json.dumps([{"id": "ad", "kind": "accuracy_drift",
                            "field": "accuracy_ratio", "factor": 0.5}]))
    with pytest.raises(RuleError, match="accuracy_drift"):
        load_rules(json.dumps([{"id": "ad", "kind": "accuracy_drift",
                                "field": "entropy_bits", "factor": 0.5}]))


def test_accuracy_drift_fires_once_with_idle_immunity():
    """The acceptance shape: the ANALYTIC bound is the baseline (no
    rolling window), healthy epochs and idle windows (ratio 0.0 = no
    observation) never fire, the drift epoch fires exactly once, and
    staying drifted does not re-fire."""
    from inspektor_gadget_tpu.alerts.engine import AlertEngine
    from inspektor_gadget_tpu.alerts.rules import load_rules

    rules = load_rules(json.dumps([{
        "id": "drift", "kind": "accuracy_drift", "factor": 0.5,
        "for": 0}]))
    eng = AlertEngine(rules, node="n0", gadget=GADGET, dry_run=True)
    base = {"events": 100, "drops": 0, "distinct": 5.0, "entropy": 1.0,
            "heavy_hitters": [], "anomaly": {}}

    def obs(epoch, ratio, now):
        return eng.observe({**base, "epoch": epoch,
                            "accuracy": {"ratio": ratio, "audited": True}},
                           now=now)

    transitions = []
    # healthy epochs inside the envelope, one idle window in the middle
    for i, r in enumerate((0.2, 0.3, 0.0, 0.25)):
        transitions += [(e.transition, i) for e in obs(i, r, 10.0 * i)]
    assert transitions == []
    # injected skew: observed error escapes half the bound → one firing
    evs = obs(4, 0.8, 40.0)
    assert [e.transition for e in evs] == ["pending", "firing"]
    assert evs[-1].rule == "drift" and evs[-1].value == 0.8
    evs2 = obs(5, 0.9, 50.0)                   # still drifted: no re-fire
    assert not any(e.transition == "firing" for e in evs2)
    eng.close()


def test_accuracy_drift_ignores_plane_off_summaries():
    from inspektor_gadget_tpu.alerts.engine import AlertEngine
    from inspektor_gadget_tpu.alerts.rules import load_rules

    rules = load_rules(json.dumps([{
        "id": "drift", "kind": "accuracy_drift", "factor": 0.1,
        "for": 0}]))
    eng = AlertEngine(rules, node="n0", gadget=GADGET, dry_run=True)
    base = {"events": 100, "drops": 0, "distinct": 5.0, "entropy": 1.0,
            "heavy_hitters": [], "anomaly": {}}
    evs = []
    for epoch in range(6):                     # plane off: no accuracy key
        evs += eng.observe({**base, "epoch": epoch}, now=10.0 * epoch)
    assert evs == []
    eng.close()


# ---------------------------------------------------------------------------
# CLI: ig-tpu fleet accuracy (stubbed request path + rendering)
# ---------------------------------------------------------------------------

class _AccArgs:
    remote = ""
    deadline = 3.0
    gadget = ""
    output = "table"

    def __init__(self, **kv):
        for k, v in kv.items():
            setattr(self, k, v)


_ACC_ROW = {
    "run_id": "run-acc-000001", "gadget": GADGET, "audited": True,
    "sample_size": 128, "ratio": 0.42, "samples_fed": 5000,
    "stats": {
        "heavy_hitters": {"bound": 0.00266, "bound_abs": 13.3,
                          "confidence": 0.95, "observed_err": 0.00112,
                          "audited": True, "audited_keys": 5},
        "distinct": {"bound": 0.065, "regime": "raw",
                     "observed_err": None, "audited": False},
    },
}


def _stub_client(rows):
    class _StubClient:
        def __init__(self, target, node, rpc_deadline=3.0):
            self.node = node

        def dump_state(self):
            return {"accuracy": rows}

        def close(self):
            pass
    return _StubClient


def test_fleet_accuracy_renders_table_and_json(monkeypatch, capsys):
    from inspektor_gadget_tpu.agent import client as agent_client
    from inspektor_gadget_tpu.cli.fleet import cmd_fleet_accuracy

    monkeypatch.setattr(agent_client, "AgentClient",
                        _stub_client([_ACC_ROW]))
    assert cmd_fleet_accuracy(_AccArgs(remote="n0=localhost:19999")) == 0
    out = capsys.readouterr().out
    assert "STAT" in out and "BOUND" in out and "OBSERVED" in out
    assert "run-acc-000001" in out
    assert "heavy_hitters" in out and "distinct" in out
    assert "0.00112" in out and "yes" in out   # audited stat renders err
    assert "-" in out and "no" in out          # unaudited stat renders dash
    assert "0.42" in out and "128" in out
    # json mode carries the rows verbatim
    assert cmd_fleet_accuracy(_AccArgs(remote="n0=localhost:19999",
                                       output="json")) == 0
    doc = json.loads(capsys.readouterr().out)
    run = doc["agents"][0]["runs"][0]
    assert run["ratio"] == 0.42
    assert run["stats"]["heavy_hitters"]["observed_err"] == 0.00112
    # --gadget filters to matching runs only
    assert cmd_fleet_accuracy(_AccArgs(remote="n0=localhost:19999",
                                       gadget="trace/open")) == 0
    assert "no audited runs" in capsys.readouterr().out


def test_fleet_accuracy_unreachable_node_is_rc1(monkeypatch, capsys):
    from inspektor_gadget_tpu.agent import client as agent_client
    from inspektor_gadget_tpu.cli.fleet import cmd_fleet_accuracy

    class _Boom:
        def __init__(self, target, node, rpc_deadline=3.0):
            raise OSError("connection refused")

    monkeypatch.setattr(agent_client, "AgentClient", _Boom)
    assert cmd_fleet_accuracy(_AccArgs(remote="n0=localhost:19999")) == 1
    assert "unreachable" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# real fleet surfaces: DumpState rows + the doctor probe
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def agents():
    from inspektor_gadget_tpu.agent.service import serve
    servers, targets = [], {}
    tmp = tempfile.mkdtemp()
    for i in range(2):
        addr = f"unix://{tmp}/acc-agent{i}.sock"
        server, _ = serve(addr, node_name=f"anode-{i}")
        servers.append(server)
        targets[f"anode-{i}"] = addr
    yield targets
    for s in servers:
        s.stop(grace=0.5)


def _audited_stats(run_id: str) -> AccuracyStats:
    rng = np.random.default_rng(5)
    keys = _zipf_stream(rng, 2000, 40)
    sh = ShadowSample(128)
    sh.update(keys)
    uk, uc = np.unique(keys, return_counts=True)
    a = AccuracyStats(run_id, GADGET)
    a.note_fed(keys.size)
    a.observe_block(accuracy_block(
        events=float(keys.size), depth=3, width=1024, hll_p=8,
        ent_log2_width=6, distinct=float(uk.size), entropy_bits=2.0,
        hh_keys=uk[:8], hh_counts=uc[:8].astype(np.int64), shadow=sh))
    return a


def test_dump_state_and_doctor_carry_accuracy_rows(agents):
    from inspektor_gadget_tpu.agent.client import AgentClient
    from inspektor_gadget_tpu.doctor import _probe_accuracy

    w0 = _probe_accuracy()
    assert w0.ok and "no audited runs" in w0.detail
    a = _audited_stats("run-acc-dump-1")
    a.register()
    try:
        client = AgentClient(next(iter(agents.values())), "anode-0")
        try:
            rows = client.dump_state()["accuracy"]
        finally:
            client.close()
        row = next(r for r in rows if r.get("run_id") == "run-acc-dump-1")
        assert row["gadget"] == GADGET and row["audited"] is True
        assert row["samples_fed"] == 2000
        assert row["stats"]["heavy_hitters"]["audited"] is True
        w = _probe_accuracy()
        assert w.ok and "run-acc-" in w.detail and "ratio" in w.detail
    finally:
        a.unregister()


# ---------------------------------------------------------------------------
# perf: bench records + harness overhead ledger (tier-1 smoke)
# ---------------------------------------------------------------------------

def test_accuracy_bench_publishes_schema_valid_records(tmp_path):
    from inspektor_gadget_tpu.perf.accuracy_bench import publish
    from inspektor_gadget_tpu.perf.compare import compare_ledger
    from inspektor_gadget_tpu.perf.ledger import read_ledger
    from inspektor_gadget_tpu.perf.schema import validate_record

    ledger = str(tmp_path / "PERF.jsonl")
    records = publish(batch=1 << 10, capacity=64, seconds=0.05,
                      events=20_000, ledger=ledger)
    assert {r["config"] for r in records} == {
        "accuracy-audit", "accuracy-overhead", "accuracy-observed-err"}
    for rec in records:
        assert validate_record(rec) == []
    over = next(r for r in records if r["config"] == "accuracy-overhead")
    assert 0.0 <= over["value"] <= 1.0
    err = next(r for r in records
               if r["config"] == "accuracy-observed-err")
    assert err["extra"]["observed_err_pct"] <= err["extra"]["bound_pct"]
    on_disk = read_ledger(ledger).records
    assert len(on_disk) == 3
    assert all(r.rc == 0 for r in compare_ledger(on_disk))


# ---------------------------------------------------------------------------
# docs lint: the err-pct claim pattern in check_perf_claims
# ---------------------------------------------------------------------------

def test_check_perf_claims_err_pct_pattern():
    from tools.check_perf_claims import Backing, check_claim, extract_claims

    claims = extract_claims(
        "the error stays well under the 1% mark\n"
        "observed error within 0.5%\n",
        "inspektor_gadget_tpu/ops/countmin.py")
    errs = [c for c in claims if c.kind == "err_pct"]
    assert [c.hi for c in errs] == [1.0, 0.5]
    ok = Backing(0.0042, "cpu", False, "PERF.jsonl:9#observed_err_pct",
                 kind="err_pct")
    # bound-style: any backing at or under the ceiling is clean, and an
    # accuracy property needs no platform labeling (cpu-exempt)
    assert check_claim(errs[0], [ok]) == ""
    # an ev/s backing with a matching number may NOT back an err claim
    assert "NO ledger" in check_claim(
        errs[0], [Backing(0.5, "cpu", False, "x")])
    # a measurement OVER the ceiling does not back the claim
    assert "NO ledger" in check_claim(
        errs[1], [Backing(1.7, "tpu", False, "y", kind="err_pct")])


def test_ledger_backings_surface_observed_err_pct(tmp_path):
    from tools.check_perf_claims import _ledger_backings

    p = tmp_path / "PERF.jsonl"
    p.write_text(json.dumps({
        "config": "accuracy-observed-err", "value": 0.0042, "unit": "pct",
        "provenance": {"platform": "cpu", "degraded": False},
        "extra": {"observed_err_pct": 0.0042}}) + "\n")
    backs = _ledger_backings(p)
    ep = [b for b in backs if b.kind == "err_pct"]
    assert len(ep) == 1
    assert ep[0].value == pytest.approx(0.0042)
    assert ep[0].source.endswith("#observed_err_pct")
