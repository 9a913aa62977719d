"""Multi-chip sharded ingest (ISSUE 14): parity, validation, harness.

The tentpole contract under test: one fused SketchBundle replica per
device lane, batches round-robined onto per-chip pinned rings, psum/pmax
collective merge at harvest ONLY — and the harvested bundle is
BIT-IDENTICAL to the single-chip fold of the same event stream, so
`window_digest`, history sealing, alerts, and replay `--verify` ride
unchanged. The 8-device topology comes from tests/conftest.py
(`--xla_force_host_platform_device_count=8` on CPU).

Candidate-exactness note: the top-k parity cases keep the key vocabulary
under the candidate-table size k, where the streaming candidate set is
exactly the distinct-key set on every path. Above k the table is a
documented approximation on ALL paths (single-chip included) and the
union-at-harvest can only widen the candidate pool.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import inspektor_gadget_tpu.all_gadgets  # noqa: F401
from inspektor_gadget_tpu.gadgets import GadgetContext, get
from inspektor_gadget_tpu.operators.operators import get as get_op
from inspektor_gadget_tpu.ops.sketches import (
    SketchBundle,
    bundle_ingest_jit,
    bundle_init,
    bundle_stack_sharded,
    make_bundle_harvest_sharded,
    make_bundle_ingest_sharded,
)
from inspektor_gadget_tpu.params import ParamError
from inspektor_gadget_tpu.parallel.mesh import NODE_AXIS, ingest_mesh
from inspektor_gadget_tpu.sources.synthetic import PySyntheticSource

KW = dict(depth=3, log2_width=9, hll_p=7, entropy_log2_width=6, k=64)
BATCH = 512


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


def _assert_bundles_bit_identical(a: SketchBundle, b: SketchBundle,
                                  ctx: str = "") -> None:
    for name, xa, xb in (
        ("cms.table", a.cms.table, b.cms.table),
        ("cms.total", a.cms.total, b.cms.total),
        ("hll.registers", a.hll.registers, b.hll.registers),
        ("entropy.counts", a.entropy.counts, b.entropy.counts),
        ("topk.keys", a.topk.keys, b.topk.keys),
        ("topk.counts", a.topk.counts, b.topk.counts),
        ("events", a.events, b.events),
        ("drops", a.drops, b.drops),
    ):
        assert np.array_equal(np.asarray(xa), np.asarray(xb)), \
            f"{ctx}: leaf {name} diverged"


def _mixed_batches(rng, count: int = 13) -> list[tuple]:
    """(keys, weights, drops) triples with ragged tails (mask shapes the
    single-chip path compiles anyway) and a vocab < k for candidate
    exactness."""
    out = []
    for i in range(count):
        n = BATCH if i % 3 else 300 + i
        keys = np.zeros(BATCH, np.uint32)
        keys[:n] = rng.integers(1, 50, n)
        w = np.zeros(BATCH, np.uint32)
        w[:n] = 1
        out.append((keys, w, float(i % 2)))
    return out


def _fold_reference(batches) -> SketchBundle:
    ref = bundle_init(**KW)
    tok = None
    for k_np, w_np, dr in batches:
        ref, tok = bundle_ingest_jit(ref, jnp.asarray(k_np),
                                     jnp.asarray(k_np), jnp.asarray(k_np),
                                     jnp.asarray(w_np), jnp.float32(dr))
    if tok is not None:
        jax.block_until_ready(tok)
    return ref


def _sharded_fold(batches, chips: int, harvest_mid: int | None = None):
    """Round-robin `batches` over a `chips`-lane mesh; returns the final
    harvested bundle (plus the mid-run harvest when asked). Tail rounds
    pad empty lanes with zero-weight fillers, exactly like the operator's
    flush."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = ingest_mesh(chips)
    like = bundle_init(**KW)
    stacked = bundle_stack_sharded(bundle_init(**KW), mesh)
    step = make_bundle_ingest_sharded(mesh, like)
    harvest = make_bundle_harvest_sharded(mesh, like)
    sh = NamedSharding(mesh, P(NODE_AXIS))
    mid = None
    i = 0
    while i < len(batches):
        round_b = list(batches[i:i + chips])
        while len(round_b) < chips:
            round_b.append((np.zeros(BATCH, np.uint32),
                            np.zeros(BATCH, np.uint32), 0.0))
        keys = jax.device_put(np.stack([b[0] for b in round_b]), sh)
        wts = jax.device_put(np.stack([b[1] for b in round_b]), sh)
        drs = jax.device_put(np.asarray([b[2] for b in round_b],
                                        np.float32), sh)
        stacked, tok = step(stacked, keys, keys, keys, wts, drs)
        i += chips
        if harvest_mid is not None and mid is None and i >= harvest_mid:
            # mid-run collective harvest: reads the live lane bundles
            # (never donates) while ingest continues after it
            mid = harvest(stacked)
    jax.block_until_ready(tok)
    return harvest(stacked), mid


def test_sharded_harvest_bit_identical_across_1_2_4_8():
    """THE acceptance anchor: every SketchBundle leaf of the collective
    harvest equals the single-chip fold across 1/2/4/8 lanes, over a
    stream with ragged tails, per-batch drops, and uneven final rounds —
    and a mid-run harvest matches the single-chip fold of the same
    prefix."""
    rng = np.random.default_rng(7)
    batches = _mixed_batches(rng)
    ref_all = _fold_reference(batches)
    for chips in (1, 2, 4, 8):
        prefix = ((len(batches) // chips) // 2) * chips or chips
        got, mid = _sharded_fold(batches, chips, harvest_mid=prefix)
        _assert_bundles_bit_identical(ref_all, got, ctx=f"chips={chips}")
        ref_prefix = _fold_reference(batches[:prefix])
        _assert_bundles_bit_identical(ref_prefix, mid,
                                      ctx=f"chips={chips} mid-run")


def test_sharded_window_digest_identical_across_device_counts():
    """History-plane determinism (ISSUE 14 satellite): a window sealed
    from the harvested state carries the SAME state-only content digest
    at every device count — replay `--verify` and byte-identical reseal
    cannot hold otherwise."""
    from inspektor_gadget_tpu.history import window_digest
    from inspektor_gadget_tpu.history.window import SealedWindow

    rng = np.random.default_rng(24)
    batches = _mixed_batches(rng, count=9)

    def seal(b: SketchBundle) -> str:
        return window_digest(SealedWindow(
            gadget="trace/parity", node="n0", run_id="r", window=1,
            start_ts=1.0, end_ts=2.0, events=int(b.events), drops=0,
            cms=np.asarray(b.cms.table, dtype=np.int32),
            hll=np.asarray(b.hll.registers, dtype=np.int32),
            ent=np.asarray(b.entropy.counts, dtype=np.float32),
            topk_keys=np.asarray(b.topk.keys),
            topk_counts=np.asarray(b.topk.counts, dtype=np.int64),
            slices={}))

    want = seal(_fold_reference(batches))
    for chips in (2, 4, 8):
        got, _ = _sharded_fold(batches, chips)
        assert seal(got) == want, f"chips={chips} window digest diverged"


# ---------------------------------------------------------------------------
# operator tier
# ---------------------------------------------------------------------------

def _make_instance(extra_params: dict, gadget_params: dict | None = None,
                   extra_ctx: dict | None = None):
    desc = get("trace", "exec")
    ctx = GadgetContext(desc, extra=dict(extra_ctx or {}))
    for k, v in (gadget_params or {}).items():
        ctx.gadget_params.set(k, v)
    op = get_op("tpusketch")
    p = op.instance_params().to_params()
    p.set("enable", "true")
    p.set("log2-width", "8")
    p.set("hll-p", "6")
    p.set("entropy-log2-width", "6")
    p.set("topk", "64")
    for k, v in extra_params.items():
        p.set(k, v)
    return op.instantiate(ctx, None, p)


@pytest.fixture()
def batches():
    src = PySyntheticSource(seed=5, vocab=40, batch_size=BATCH)
    return [src.generate(BATCH) for _ in range(10)]


def test_operator_sharded_summary_matches_single_chip(batches):
    """Uneven round-robin fills through the REAL operator: 10 batches
    over 4 lanes (two full rounds + a flushed partial), harvested twice
    (mid-run + teardown) — summaries identical to the unsharded
    instance's, heavy hitters included."""
    ref = _make_instance({})
    for b in batches[:6]:
        ref.enrich_batch(b)
    s_ref_mid = ref.harvest()
    for b in batches[6:]:
        ref.enrich_batch(b)
    s_ref = ref.harvest()
    ref.post_gadget_run()

    for chips in ("2", "4", "auto"):
        inst = _make_instance({"shard-ingest": "true", "chips": chips})
        assert inst.device_view()["lanes"] >= 2
        for b in batches[:6]:
            inst.enrich_batch(b)
        s_mid = inst.harvest()
        for b in batches[6:]:
            inst.enrich_batch(b)
        s = inst.harvest()
        for got, want in ((s_mid, s_ref_mid), (s, s_ref)):
            assert got.events == want.events
            assert got.drops == want.drops
            assert got.distinct == want.distinct
            assert got.entropy_bits == want.entropy_bits
            assert got.heavy_hitters == want.heavy_hitters
        inst.post_gadget_run()


def test_operator_sharded_deterministic_across_runs(batches):
    """Two fresh sharded instances over the same batch stream produce the
    same summary sequence — the determinism replay `--verify` leans on
    (round-robin assignment and flush boundaries are functions of the
    stream alone)."""
    def run():
        inst = _make_instance({"shard-ingest": "true", "chips": "4"})
        out = []
        for i, b in enumerate(batches):
            inst.enrich_batch(b)
            if (i + 1) % 3 == 0:
                s = inst.harvest()
                out.append((s.events, s.distinct, s.entropy_bits,
                            tuple(s.heavy_hitters)))
        inst.post_gadget_run()
        return out

    assert run() == run()


def test_chips_one_is_the_exact_unsharded_path(batches):
    """chips=1 dispatch pin (zero regression risk): no mesh, no sharded
    state, the PR-7 single-pool path — and the same summary."""
    ref = _make_instance({})
    one = _make_instance({"shard-ingest": "true", "chips": "1"})
    view = one.device_view()
    assert view["lanes"] == 1 and view["harvest"] is None
    for b in batches:
        ref.enrich_batch(b)
        one.enrich_batch(b)
    assert one._pool is not None and not one._lane_pools
    s_ref, s_one = ref.harvest(), one.harvest()
    assert (s_one.events, s_one.heavy_hitters) == \
        (s_ref.events, s_ref.heavy_hitters)
    ref.post_gadget_run()
    one.post_gadget_run()


def test_ingest_folded_rides_the_sharded_lanes():
    """The zero-copy SoA path under sharding: folded_block() hands out
    the next lane's pinned block and the absorbed totals match the
    unsharded fold."""
    from inspektor_gadget_tpu.sources.batch import FoldedBatch

    inst = _make_instance({"shard-ingest": "true", "chips": "2"})
    total = 0
    for i in range(5):  # odd count: last round flushes a filler lane
        block = inst.folded_block()
        n = 200 + i
        block[0][:n] = np.arange(1, n + 1, dtype=np.uint32)
        block[1][:n] = 1
        inst.ingest_folded(FoldedBatch(lanes=block, count=n))
        total += n
    s = inst.harvest()
    assert s.events == total
    inst.post_gadget_run()


def test_sharded_harvest_under_ingest_pressure():
    """Cross-thread flush safety (the review-hardened path): harvests —
    which flush the open round with cached zero-lane fillers and run the
    collective — fire from this thread while a pump thread keeps
    staging batches onto the lane stagers lock-free. The flush must
    never touch stager state the capture thread mutates, so no torn
    slots, no lost fences, no errors, and events keep growing."""
    import threading
    import time as _time

    inst = _make_instance({"shard-ingest": "true", "chips": "4"})
    src = PySyntheticSource(seed=11, vocab=40, batch_size=BATCH)
    stop = threading.Event()
    errors: list = []
    # compile the sharded step and the harvest before the clock starts:
    # the 1.5 s window below is for the race, not for XLA
    for _ in range(4):
        inst.enrich_batch(src.generate(BATCH))
    inst.harvest()

    def pump():
        try:
            while not stop.is_set():
                inst.enrich_batch(src.generate(BATCH))
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    t = threading.Thread(target=pump)
    t.start()
    try:
        deadline = _time.monotonic() + 1.5
        last = -1
        harvests = 0
        while _time.monotonic() < deadline:
            s = inst.harvest()
            assert s.events >= last
            last = s.events
            harvests += 1
    finally:
        stop.set()
        t.join(timeout=10.0)
    assert not errors, errors
    assert harvests > 0 and last > 0
    inst.post_gadget_run()


# ---------------------------------------------------------------------------
# loud validation (FetchWindows discipline: typed errors before batch 1)
# ---------------------------------------------------------------------------

def test_chips_beyond_local_devices_is_a_param_error():
    with pytest.raises(ParamError, match="exceeds"):
        _make_instance({"shard-ingest": "true", "chips": "99"})
    # chips is validated against the host even without shard-ingest
    with pytest.raises(ParamError, match="exceeds"):
        _make_instance({"chips": "99"})


def test_shard_ingest_on_one_device_host_is_a_param_error(monkeypatch):
    import inspektor_gadget_tpu.operators.tpusketch as T
    monkeypatch.setattr(T, "_local_device_count", lambda: 1)
    with pytest.raises(ParamError, match=">= 2 local devices"):
        _make_instance({"shard-ingest": "true"})


def test_non_divisible_batch_size_is_a_param_error():
    with pytest.raises(ParamError, match="not divisible"):
        _make_instance({"shard-ingest": "true", "chips": "3"},
                       gadget_params={"batch-size": "1000"})


def test_chips_param_rejects_garbage_loudly():
    with pytest.raises(ParamError, match="integer or 'auto'"):
        _make_instance({"chips": "banana"})
    with pytest.raises(ParamError, match=">= 1"):
        _make_instance({"chips": "0"})


def test_ig_shard_disable_escape_hatch(monkeypatch, batches):
    monkeypatch.setenv("IG_SHARD_DISABLE", "1")
    inst = _make_instance({"shard-ingest": "true", "chips": "4"})
    assert inst.device_view()["lanes"] == 1
    inst.enrich_batch(batches[0])
    view = inst.device_view()
    assert view["harvest"] is None and len(view["lane_devices"]) == 1
    inst.post_gadget_run()
    # the hatch outranks the topology checks: a fleet-wide chips=N
    # config must still start on a host that degraded below N devices
    # when the operator forces the single-chip path
    inst2 = _make_instance({"shard-ingest": "true", "chips": "99"})
    assert inst2.device_view()["lanes"] == 1
    inst2.post_gadget_run()


# ---------------------------------------------------------------------------
# the two entries of the one dispatch
# ---------------------------------------------------------------------------

HISTORY_ON = {"history": "true", "history-interval": "0"}
ENTRY_CASES = {
    "base": {},
    "history": HISTORY_ON,
    "quantiles": {**HISTORY_ON, "quantiles": "true"},
    "invertible": {**HISTORY_ON, "invertible": "true"},
    "classes": {**HISTORY_ON, "invertible": "true",
                "priority-classes": "hot=6:101|102,rest=6:*"},
}
# what a sealed window carries only through enrich_batch (slices and the
# label sample's names need an EventBatch's columns; the digest covers
# the slices) or that names the instance, not the state
WINDOW_FIELDS_APART = {"slices", "slices_dropped", "names", "digest",
                       "seq", "run_id"}


def _entry_stream(rng, count: int = 9):
    """(keys, mntns, values, cumulative drops) per batch: ragged tails,
    drops that grow on every second batch, three tenants, magnitudes that
    span DDSketch buckets and include zeros."""
    out, drops = [], 0
    for i in range(count):
        n = BATCH if i % 3 else 300 + i
        drops += (i % 2) * (i + 1)
        out.append((rng.integers(1, 50, n).astype(np.uint32),
                    rng.choice([101, 102, 777], n).astype(np.uint32),
                    rng.integers(0, 1 << 20, n).astype(np.uint32), drops))
    return out


def _leaves(tree) -> list:
    return [(jax.tree_util.keystr(path), np.asarray(leaf))
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)]


def _drive_entry(entry: str, params: dict, stream, tmp_path) -> dict:
    """Feed `stream` through one entry of a fresh instance, sealing a
    window after the fifth batch and after the last; returns every leaf
    of the state it left and the windows it sealed."""
    from inspektor_gadget_tpu.history import HISTORY
    from inspektor_gadget_tpu.history.window import decode_window
    from inspektor_gadget_tpu.sources.batch import EventBatch, FoldedBatch

    ticks = iter(range(1, 1000))
    inst = _make_instance(
        {"harvest-interval": "1h", "history-dir": str(tmp_path / entry),
         **params},
        extra_ctx={"history_clock": lambda: float(next(ticks))})
    hist = "history" in params
    got = {}
    for i, (keys, mntns, values, drops) in enumerate(stream):
        n = len(keys)
        if entry == "folded":
            block = inst.folded_block()
            block[0][:n], block[1][:n], block[2][:n] = keys, 1, mntns
            block[3][:n] = values
            inst.ingest_folded(FoldedBatch(
                lanes=block, count=n, drops=drops,
                has_values="quantiles" in params))
        else:
            b = EventBatch.alloc(BATCH, with_comm=False)
            b.cols["key_hash"][:n] = keys      # under 2^32: folds to itself
            b.cols["mntns"][:n] = mntns
            b.cols["aux1"][:n] = values
            b.cols["ts"][:n] = 1
            b.count, b.drops = n, drops
            inst.enrich_batch(b)
        if hist and i in (4, len(stream) - 1):
            if i == 4:
                got["window_planes"] = _leaves(inst._wplanes)
            inst.seal_window()
    with inst._bundle_mu:
        got["bundle"] = _leaves(inst._merged_locked())
        got["classes"] = _leaves([s for _, s in inst._inv_classes])
    inst.post_gadget_run()
    if hist:
        got["windows"] = [
            {f.name: getattr(w, f.name) for f in dataclasses.fields(w)
             if f.name not in WINDOW_FIELDS_APART}
            for w in (decode_window(h, payload) for h, payload in
                      HISTORY.fetch_windows(base_dir=str(tmp_path / entry)))]
        assert len(got["windows"]) == 2
    return got


@pytest.mark.parametrize("lanes", ["1", "4"])
@pytest.mark.parametrize("case", ENTRY_CASES)
def test_folded_entry_leaves_the_state_enrich_batch_leaves(case, lanes,
                                                           tmp_path):
    """`enrich_batch` (an EventBatch whose three key columns are one
    column) and `ingest_folded` are two adapters in front of one
    dispatch: the same keys, weights and drops leave every bundle leaf,
    the class sketches, the window CMS and HLL and every sealed window
    bit-equal, with each plane on, on one lane and on four."""
    params = dict(ENTRY_CASES[case])
    if lanes != "1":
        params.update({"shard-ingest": "true", "chips": lanes})
    stream = _entry_stream(np.random.default_rng(29))
    want = _drive_entry("batch", params, stream, tmp_path)
    got = _drive_entry("folded", params, stream, tmp_path)
    np.testing.assert_equal(got, want)
