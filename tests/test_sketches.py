"""Sketch-plane tests: accuracy bounds, mergeability, static-shape jit.

Accuracy targets from BASELINE.md: <1% heavy-hitter error; HLL standard
error ~1.04/sqrt(m) (p=14 → ~0.8%).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from inspektor_gadget_tpu.ops import (
    bundle_init, bundle_update, bundle_merge,
    cms_init, cms_update, cms_query, cms_merge,
    hll_init, hll_update, hll_estimate, hll_merge,
    entropy_init, entropy_update, entropy_estimate, entropy_merge,
    topk_init, topk_update, topk_merge,
    fold64_to_32,
)
from inspektor_gadget_tpu.ops.sketches import bundle_update_jit


def zipf_keys(rng, n, vocab=1000, a=1.5):
    return rng.zipf(a, size=n).clip(1, vocab).astype(np.uint32) * np.uint32(2654435761)


def test_fold64():
    k = np.array([0x123456789ABCDEF0], dtype=np.uint64)
    assert fold64_to_32(k)[0] == np.uint32(0x12345678 ^ 0x9ABCDEF0)


# -- count-min ---------------------------------------------------------------

def test_cms_exact_on_sparse():
    cms = cms_init(depth=4, log2_width=12)
    keys = jnp.array([1, 2, 3, 1, 1, 2], dtype=jnp.uint32)
    cms = cms_update(cms, keys)
    q = cms_query(cms, jnp.array([1, 2, 3, 99], dtype=jnp.uint32))
    assert q[0] == 3 and q[1] == 2 and q[2] == 1
    assert q[3] <= 1  # overestimate only, tiny on sparse table
    assert float(cms.total) == 6


def test_cms_weighted_and_masked():
    cms = cms_init(depth=4, log2_width=10)
    keys = jnp.array([5, 5, 7, 7], dtype=jnp.uint32)
    w = jnp.array([2, 3, 1, 0], dtype=jnp.int32)  # last slot masked out
    cms = cms_update(cms, keys, w)
    q = cms_query(cms, jnp.array([5, 7], dtype=jnp.uint32))
    assert q[0] == 5 and q[1] == 1


def test_cms_heavy_hitter_error_under_1pct():
    rng = np.random.default_rng(0)
    keys = zipf_keys(rng, 200_000)
    cms = cms_init(depth=4, log2_width=16)
    cms = cms_update(cms, jnp.asarray(keys))
    uniq, exact = np.unique(keys, return_counts=True)
    heavy = exact >= 0.001 * len(keys)
    est = np.asarray(cms_query(cms, jnp.asarray(uniq)))
    rel_err = np.abs(est[heavy] - exact[heavy]) / exact[heavy]
    assert rel_err.max() < 0.01


def test_cms_merge_equals_union():
    rng = np.random.default_rng(1)
    k1, k2 = zipf_keys(rng, 5000), zipf_keys(rng, 5000)
    a = cms_update(cms_init(4, 14), jnp.asarray(k1))
    b = cms_update(cms_init(4, 14), jnp.asarray(k2))
    merged = cms_merge(a, b)
    union = cms_update(cms_update(cms_init(4, 14), jnp.asarray(k1)), jnp.asarray(k2))
    assert jnp.array_equal(merged.table, union.table)


# -- HLL ---------------------------------------------------------------------

def test_hll_estimate_within_2pct():
    rng = np.random.default_rng(2)
    n = 50_000
    keys = rng.integers(0, 2**32, size=n, dtype=np.uint32)
    distinct = len(np.unique(keys))
    h = hll_update(hll_init(p=14), jnp.asarray(keys))
    est = float(hll_estimate(h))
    assert abs(est - distinct) / distinct < 0.02


def test_hll_small_range_linear_counting():
    keys = jnp.arange(1, 101, dtype=jnp.uint32) * jnp.uint32(2654435761)
    h = hll_update(hll_init(p=12), keys)
    est = float(hll_estimate(h))
    assert abs(est - 100) < 3


def test_hll_merge_is_union():
    rng = np.random.default_rng(3)
    k1 = rng.integers(0, 2**32, 10_000, dtype=np.uint32)
    k2 = rng.integers(0, 2**32, 10_000, dtype=np.uint32)
    a = hll_update(hll_init(12), jnp.asarray(k1))
    b = hll_update(hll_init(12), jnp.asarray(k2))
    m = hll_merge(a, b)
    both = hll_update(hll_update(hll_init(12), jnp.asarray(k1)), jnp.asarray(k2))
    assert jnp.array_equal(m.registers, both.registers)


def test_hll_mask():
    keys = jnp.arange(1, 65, dtype=jnp.uint32)
    mask = jnp.arange(64) < 32
    h = hll_update(hll_init(12), keys, mask)
    assert abs(float(hll_estimate(h)) - 32) < 3


# -- entropy -----------------------------------------------------------------

def test_entropy_uniform_vs_skewed():
    uniform = jnp.arange(256, dtype=jnp.uint32)
    e1 = entropy_update(entropy_init(12), uniform)
    constant = jnp.zeros(256, dtype=jnp.uint32) + 7
    e2 = entropy_update(entropy_init(12), constant)
    h1, h2 = float(entropy_estimate(e1)), float(entropy_estimate(e2))
    assert abs(h1 - 8.0) < 0.2  # 256 distinct → ~8 bits
    assert h2 == pytest.approx(0.0, abs=1e-5)


def test_entropy_merge_additive():
    a = entropy_update(entropy_init(10), jnp.array([1, 2], dtype=jnp.uint32))
    b = entropy_update(entropy_init(10), jnp.array([2, 3], dtype=jnp.uint32))
    m = entropy_merge(a, b)
    assert float(m.counts.sum()) == 4


# -- top-k -------------------------------------------------------------------

def test_topk_finds_true_heavy_hitters():
    rng = np.random.default_rng(4)
    keys = zipf_keys(rng, 100_000, vocab=5000)
    uniq, exact = np.unique(keys, return_counts=True)
    true_top = set(uniq[np.argsort(-exact)[:10]].tolist())
    cms = cms_init(4, 16)
    tk = topk_init(64)
    for i in range(0, len(keys), 8192):
        chunk = np.zeros(8192, dtype=np.uint32)
        got = keys[i:i + 8192]
        chunk[: len(got)] = got
        mask = jnp.arange(8192) < len(got)
        cms = cms_update(cms, jnp.asarray(chunk), mask.astype(jnp.int32))
        tk = topk_update(tk, cms, jnp.asarray(chunk), mask)
    got_top = set(np.asarray(tk.keys)[np.argsort(-np.asarray(tk.counts))[:10]].tolist())
    assert len(true_top & got_top) >= 9  # ≥90% of top-10 recovered


def test_topk_dedupes_and_sorts():
    cms = cms_init(4, 12)
    keys = jnp.array([10, 10, 10, 20, 20, 30], dtype=jnp.uint32)
    cms = cms_update(cms, keys)
    tk = topk_update(topk_init(4), cms, keys)
    kk = np.asarray(tk.keys)
    assert len(set(kk[kk != 0].tolist())) == len(kk[kk != 0])  # unique
    order = np.argsort(-np.asarray(tk.counts))
    assert kk[order[0]] == 10


def test_topk_merge():
    cms = cms_init(4, 12)
    k1 = jnp.array([1, 1, 1], dtype=jnp.uint32)
    k2 = jnp.array([2, 2, 2, 2], dtype=jnp.uint32)
    cms = cms_update(cms_update(cms, k1), k2)
    a = topk_update(topk_init(4), cms, k1)
    b = topk_update(topk_init(4), cms, k2)
    m = topk_merge(a, b, cms)
    order = np.argsort(-np.asarray(m.counts))
    assert np.asarray(m.keys)[order[0]] == 2


# -- bundle ------------------------------------------------------------------

def test_bundle_update_and_merge():
    rng = np.random.default_rng(5)
    keys = jnp.asarray(zipf_keys(rng, 4096))
    mask = jnp.ones(4096, dtype=bool)
    b1 = bundle_update(bundle_init(), keys, keys, keys, mask)
    b2 = bundle_update(bundle_init(), keys, keys, keys, mask)
    m = bundle_merge(b1, b2)
    assert float(m.events) == 8192
    assert float(m.cms.total) == 8192


def test_bundle_update_jit_donation():
    b = bundle_init(log2_width=12, hll_p=10, entropy_log2_width=8, k=16)
    keys = jnp.arange(256, dtype=jnp.uint32)
    mask = jnp.ones(256, dtype=bool)
    b = bundle_update_jit(b, keys, keys, keys, mask)
    b = bundle_update_jit(b, keys, keys, keys, mask)
    assert float(b.events) == 512


# -- sliding window (TTL semantics on device) --------------------------------

def test_windowed_cms_ttl_semantics():
    from inspektor_gadget_tpu.ops.window import (
        wcms_advance, wcms_init, wcms_query, wcms_update)

    w = wcms_init(n_slots=3, depth=4, log2_width=10)
    k7 = jnp.array([7, 7], dtype=jnp.uint32)
    k9 = jnp.array([9], dtype=jnp.uint32)
    w = wcms_update(w, k7)          # epoch 0: 7 -> 2
    w = wcms_advance(w)
    w = wcms_update(w, k9)          # epoch 1: 9 -> 1
    q = wcms_query(w, jnp.array([7, 9], dtype=jnp.uint32))
    assert q[0] == 2 and q[1] == 1  # both epochs live
    # only last 1 epoch: 7 aged out of scope
    q1 = wcms_query(w, jnp.array([7, 9], dtype=jnp.uint32), last_k=1)
    assert q1[0] == 0 and q1[1] == 1
    # rotate twice more: epoch-0 slot is dropped entirely
    w = wcms_advance(w)
    w = wcms_advance(w)             # wraps onto slot 0, zeroing it
    q = wcms_query(w, jnp.array([7], dtype=jnp.uint32))
    assert q[0] == 0


def test_wcms_merge_associative_and_commutative():
    """The history plane's lazy query-time fold reorders and regroups
    merges freely (per-node, per-window, chunked fetches) — legal only
    because slot-wise merge is a commutative monoid. Assert it on real
    updated states, not axioms."""
    from inspektor_gadget_tpu.ops.window import (
        wcms_init, wcms_merge, wcms_update)

    rng = np.random.default_rng(11)
    states = []
    for _ in range(3):
        s = wcms_init(n_slots=4, depth=4, log2_width=10)
        s = wcms_update(s, jnp.asarray(zipf_keys(rng, 2048)))
        states.append(s)
    a, b, c = states
    ab_c = wcms_merge(wcms_merge(a, b), c)
    a_bc = wcms_merge(a, wcms_merge(b, c))
    assert jnp.array_equal(ab_c.slots, a_bc.slots)
    ba = wcms_merge(b, a)
    ab = wcms_merge(a, b)
    assert jnp.array_equal(ab.slots, ba.slots)


def test_wcms_psum_equals_pairwise_merge():
    """Cluster-wide wcms_psum over a named axis must agree with the
    host-side pairwise merge — the two merge paths (device all-reduce
    vs client-side fold over fetched windows) may never diverge."""
    from inspektor_gadget_tpu.ops.window import (
        wcms_init, wcms_merge, wcms_psum, wcms_update)

    rng = np.random.default_rng(12)
    a = wcms_update(wcms_init(n_slots=2, depth=4, log2_width=10),
                    jnp.asarray(zipf_keys(rng, 1024)))
    b = wcms_update(wcms_init(n_slots=2, depth=4, log2_width=10),
                    jnp.asarray(zipf_keys(rng, 1024)))
    stacked = jax.tree.map(lambda x, y: jnp.stack([x, y]), a, b)
    out = jax.vmap(lambda s: wcms_psum(s, "nodes"),
                   axis_name="nodes")(stacked)
    want = wcms_merge(a, b)
    assert jnp.array_equal(out.slots[0], want.slots)
    assert jnp.array_equal(out.slots[1], want.slots)


def test_range_query_answers_are_split_invariant():
    """Order-invariance over random window splits: the same key stream
    chopped into arbitrary per-window sketches and merged in any
    grouping must answer range queries like one single-pass sketch —
    exactly for the additive planes (CMS/entropy), within documented
    sketch error for HLL."""
    from inspektor_gadget_tpu.history import merge_windows
    from inspektor_gadget_tpu.history.window import SealedWindow
    from inspektor_gadget_tpu.ops.entropy import (
        entropy_estimate, entropy_init, entropy_update)
    from inspektor_gadget_tpu.ops.hll import hll_estimate, hll_init, hll_update

    rng = np.random.default_rng(13)
    keys = zipf_keys(rng, 60_000, vocab=3000)

    def window_of(chunk: np.ndarray, i: int) -> SealedWindow:
        cms = cms_update(cms_init(4, 12), jnp.asarray(chunk))
        h = hll_update(hll_init(10), jnp.asarray(chunk))
        e = entropy_update(entropy_init(8), jnp.asarray(chunk))
        uniq, counts = np.unique(chunk, return_counts=True)
        order = np.argsort(-counts)[:16]
        return SealedWindow(
            gadget="t", node="n", run_id="r", window=i,
            start_ts=float(i), end_ts=float(i + 1),
            events=len(chunk), drops=0,
            cms=np.asarray(cms.table), hll=np.asarray(h.registers),
            ent=np.asarray(e.counts),
            topk_keys=uniq[order].astype(np.uint32),
            topk_counts=counts[order].astype(np.int64),
            slices={})

    # ground truth: ONE sketch over the whole stream
    truth = window_of(keys, 0)
    true_distinct = len(np.unique(keys))

    # random splits, merged in shuffled order and random groupings
    for trial in range(3):
        trng = np.random.default_rng(100 + trial)
        cuts = np.sort(trng.choice(np.arange(1, len(keys)),
                                   size=trng.integers(3, 9), replace=False))
        chunks = np.split(keys, cuts)
        wins = [window_of(c, i) for i, c in enumerate(chunks) if len(c)]
        trng.shuffle(wins)
        # random grouping: fold a random prefix first, then the rest
        k = int(trng.integers(1, len(wins))) if len(wins) > 1 else 1
        merged = merge_windows(
            [w for grp in (wins[:k], wins[k:]) for w in grp])
        assert not merged.skipped
        # additive planes reproduce the single-pass sketch EXACTLY
        assert np.array_equal(merged.cms, truth.cms.astype(np.int64))
        assert np.array_equal(merged.ent, truth.ent.astype(np.float64))
        assert merged.events == len(keys)
        # HLL max-merge over a partition reproduces the single-pass
        # registers EXACTLY (max over sub-maxima = max over all), so the
        # merged answer IS the single-merge ground truth; the estimate
        # itself sits within the p=10 sketch's documented ~3.3% error
        assert np.array_equal(merged.hll, truth.hll)
        est = merged.distinct()
        assert abs(est - true_distinct) / true_distinct < 0.1, (
            trial, est, true_distinct)
        single = merge_windows([truth])
        assert abs(merged.entropy_bits() - single.entropy_bits()) < 1e-9
        assert est == single.distinct()


# -- fused bundle_update parity (ISSUE 10 tentpole) --------------------------
# The fused Pallas kernel must be BIT-IDENTICAL to the separate reference
# ops — CMS table, HLL registers, entropy counts, top-k state, totals.
# On CPU CI the kernel itself runs in the Pallas interpreter
# (_bundle_update_pallas(interpret=True)); on TPU the same code path is
# the production fused step.

_BUNDLE_LEAVES = ("cms.table", "cms.total", "hll.registers",
                  "entropy.counts", "topk.keys", "topk.counts",
                  "events", "drops")


def _leaf(bundle, dotted):
    out = bundle
    for part in dotted.split("."):
        out = getattr(out, part)
    return np.asarray(out)


def _assert_bundles_bit_identical(a, b, ctx=""):
    for name in _BUNDLE_LEAVES:
        assert np.array_equal(_leaf(a, name), _leaf(b, name)), (ctx, name)


def _streams(rng, n):
    return (jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint32)),
            jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint32)),
            jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint32)))


def test_fused_kernel_bit_identical_across_widths_and_masks():
    """Interpret-mode fused kernel vs the reference composition across
    sketch widths, depths, and ragged (odd-count) masks."""
    from inspektor_gadget_tpu.ops.sketches import _bundle_update_pallas

    rng = np.random.default_rng(21)
    cases = [  # (depth, log2w, ent_log2w, hll_p, n, valid)
        (4, 10, 8, 8, 256, 256),
        (2, 12, 10, 7, 512, 501),   # odd valid count under the pad mask
        (5, 11, 6, 10, 512, 384),
    ]
    for depth, log2w, entw, p, n, valid in cases:
        b0 = bundle_init(depth=depth, log2_width=log2w, hll_p=p,
                         entropy_log2_width=entw, k=16)
        hh, distinct, dist = _streams(rng, n)
        mask = jnp.asarray(np.arange(n) < valid)
        drops = jnp.float32(2)
        ref = bundle_update(b0, hh, distinct, dist, mask, drops)
        fused = _bundle_update_pallas(b0, hh, distinct, dist, mask, drops,
                                      interpret=True)
        _assert_bundles_bit_identical(ref, fused, ctx=(depth, log2w, entw, p))
        # and a second absorbed batch on top of live state
        hh2, d2, dd2 = _streams(rng, n)
        ref2 = bundle_update(ref, hh2, d2, dd2, mask)
        fused2 = _bundle_update_pallas(fused, hh2, d2, dd2, mask,
                                       interpret=True)
        _assert_bundles_bit_identical(ref2, fused2, ctx="second batch")


def test_fused_dispatch_selection_and_fallback():
    """bundle_update_fused picks the kernel only for aligned shapes on a
    TPU backend; odd batches and narrow configs take the reference path
    — and the entry point's result equals bundle_update either way."""
    from inspektor_gadget_tpu.ops import bundle_update_fused, fused_supported

    b = bundle_init(depth=4, log2_width=12, hll_p=10,
                    entropy_log2_width=8, k=8)
    assert fused_supported(b, 512)
    assert not fused_supported(b, 999)        # odd batch size
    narrow = bundle_init(depth=4, log2_width=8, hll_p=6,
                         entropy_log2_width=6, k=8)
    assert not fused_supported(narrow, 512)   # widest plane < one tile
    rng = np.random.default_rng(22)
    for n in (999, 512):                      # ragged AND aligned
        hh, distinct, dist = _streams(rng, n)
        mask = jnp.asarray(np.arange(n) < n - 7)
        ref = bundle_update(b, hh, distinct, dist, mask)
        got = bundle_update_fused(b, hh, distinct, dist, mask)
        _assert_bundles_bit_identical(ref, got, ctx=n)


# the operator's default geometry, and a narrow one from the table of
# ops.sketches.fused_expected_to_win where the kernel measured faster
_DEFAULT_GEOMETRY = dict(depth=4, log2_width=16, hll_p=14,
                         entropy_log2_width=12, k=128)
_KERNEL_GEOMETRY = dict(_DEFAULT_GEOMETRY, log2_width=12, hll_p=12)


@pytest.mark.parametrize("geometry,n,backend,disabled,arm", [
    pytest.param(_DEFAULT_GEOMETRY, 65536, "tpu", False, "scatter",
                 id="default-65536"),
    pytest.param(_DEFAULT_GEOMETRY, 8192, "tpu", False, "scatter",
                 id="default-8192"),
    pytest.param(dict(_DEFAULT_GEOMETRY, inv_rows=3), 65536, "tpu", False,
                 "scatter", id="default+invertible"),
    pytest.param(dict(_DEFAULT_GEOMETRY, quantiles=True), 65536, "tpu",
                 False, "scatter", id="default+quantiles"),
    pytest.param(dict(_DEFAULT_GEOMETRY, log2_width=12), 65536, "tpu",
                 False, "scatter", id="narrow-cms-default-hll"),
    pytest.param(dict(_DEFAULT_GEOMETRY, log2_width=13, hll_p=13), 8192,
                 "tpu", False, "scatter", id="widest-2^13"),
    pytest.param(_KERNEL_GEOMETRY, 65536, "tpu", False, "fused",
                 id="narrow-65536"),
    pytest.param(dict(_KERNEL_GEOMETRY, inv_rows=3, quantiles=True), 65536,
                 "tpu", False, "fused", id="narrow+invertible+quantiles"),
    pytest.param(_KERNEL_GEOMETRY, 8192, "tpu", False, "fused",
                 id="narrow-8192"),
    pytest.param(_KERNEL_GEOMETRY, 999, "tpu", False, "scatter",
                 id="odd-batch"),
    pytest.param(dict(depth=4, log2_width=8, hll_p=6, entropy_log2_width=6,
                      k=8), 512, "tpu", False, "scatter",
                 id="under-one-tile"),
    pytest.param(_KERNEL_GEOMETRY, 65536, "tpu", True, "scatter",
                 id="IG_FUSED_DISABLE"),
    pytest.param(_KERNEL_GEOMETRY, 65536, "cpu", False, "scatter",
                 id="cpu"),
])
def test_update_arm_selection(monkeypatch, geometry, n, backend, disabled,
                              arm):
    """(geometry, batch) -> arm: the kernel only on a TPU, for aligned
    shapes, where it is expected to win and is not disabled; and
    bundle_update_fused takes the arm update_arm names."""
    from inspektor_gadget_tpu.ops import sketches

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if disabled:
        monkeypatch.setenv("IG_FUSED_DISABLE", "1")
    else:
        monkeypatch.delenv("IG_FUSED_DISABLE", raising=False)
    b = bundle_init(**geometry)
    assert sketches.update_arm(b, n) == arm
    took = []
    monkeypatch.setattr(sketches, "_bundle_update_pallas",
                        lambda bundle, *a, **kw: took.append("fused"))
    monkeypatch.setattr(sketches, "bundle_update",
                        lambda bundle, *a, **kw: took.append("scatter"))
    keys = jnp.zeros(n, jnp.uint32)
    sketches.bundle_update_fused(b, keys, keys, keys, jnp.ones(n, bool))
    assert took == [arm]


@pytest.mark.parametrize("settings", [
    pytest.param({"log2-width": "12", "hll-p": "12"}, id="kernel-geometry"),
    pytest.param({"log2-width": "10", "hll-p": "8",
                  "entropy-log2-width": "8", "quantiles": "true"},
                 id="quantiles"),
])
def test_operator_names_the_arm_the_dispatcher_took(settings):
    """The arm counter counts every step under the arm its step lowers
    to, and every summary's pipeline block names it (here, on the CPU,
    the scatter composition whatever the geometry)."""
    import inspektor_gadget_tpu.all_gadgets  # noqa: F401
    from inspektor_gadget_tpu.gadgets import GadgetContext, get
    from inspektor_gadget_tpu.operators import tpusketch
    from inspektor_gadget_tpu.operators.operators import get as get_op
    from inspektor_gadget_tpu.ops.pallas_kernels import (FUSED_KERNEL_NAME,
                                                         kernel_in_lowered)
    from inspektor_gadget_tpu.params import Collection
    from inspektor_gadget_tpu.runtime.local import LocalRuntime
    from inspektor_gadget_tpu.telemetry import snapshot

    def counters() -> dict:
        return {k: v for k, v in snapshot().items() if k.startswith((
            "ig_tpusketch_update_arm_steps_total{",
            "ig_tpusketch_steps_total{"))}

    desc = get("trace", "exec")
    params = desc.params().to_params()
    for k, v in (("source", "pysynthetic"), ("rate", "50000"),
                 ("batch-size", "512")):
        params.set(k, v)
    sp = get_op("tpusketch").instance_params().to_params()
    for k, v in {"enable": "true", "topk": "16",
                 "harvest-interval": "50ms", **settings}.items():
        sp.set(k, v)
    ops = Collection()
    ops["operator.tpusketch."] = sp
    summaries: list = []
    lowered: list[str] = []

    def on_summary(s) -> None:
        summaries.append(s)
        if not lowered:
            (inst,) = tpusketch.live_instances()
            step, args = inst.device_view()["step"]
            lowered.append(step.lower(*args).as_text())
        if len(summaries) >= 3:
            ctx.cancel()

    ctx = GadgetContext(desc, gadget_params=params, operator_params=ops,
                        timeout=60.0, extra={"on_sketch_summary": on_summary})
    before = counters()
    result = LocalRuntime().run_gadget(ctx)
    assert not result.errors(), result.errors()
    took = ("fused" if kernel_in_lowered(lowered[0], FUSED_KERNEL_NAME)
            else "scatter")
    assert took == "scatter"
    delta = {k: v - before.get(k, 0.0) for k, v in counters().items()
             if v != before.get(k, 0.0)}
    steps = delta.pop('ig_tpusketch_steps_total{gadget="trace/exec"}')
    assert steps > 0
    assert delta == {'ig_tpusketch_update_arm_steps_total'
                     f'{{gadget="trace/exec",arm="{took}"}}': steps}
    assert [s.pipeline["update_arm"] for s in summaries] == \
        [took] * len(summaries)


def test_fused_update_under_vmap_and_psum_merge():
    """Per-node fused updates must vmap cleanly and their states must
    merge exactly like reference states — both by pairwise bundle_merge
    and by the device psum/pmax collectives over a named axis."""
    from inspektor_gadget_tpu.ops import bundle_update_fused
    from inspektor_gadget_tpu.ops.countmin import cms_psum
    from inspektor_gadget_tpu.ops.entropy import entropy_psum
    from inspektor_gadget_tpu.ops.hll import hll_pmax

    rng = np.random.default_rng(23)
    n = 512
    b0 = bundle_init(depth=4, log2_width=10, hll_p=8,
                     entropy_log2_width=8, k=16)
    k1, _, _ = _streams(rng, n)
    k2, _, _ = _streams(rng, n)
    mask = jnp.ones(n, bool)

    stacked0 = jax.tree.map(lambda x: jnp.stack([x, x]), b0)
    keys = jnp.stack([k1, k2])
    out = jax.vmap(lambda b, k: bundle_update_fused(b, k, k, k, mask))(
        stacked0, keys)
    ref1 = bundle_update(b0, k1, k1, k1, mask)
    ref2 = bundle_update(b0, k2, k2, k2, mask)
    for i, ref in enumerate((ref1, ref2)):
        got = jax.tree.map(lambda x: x[i], out)
        _assert_bundles_bit_identical(ref, got, ctx=f"vmap lane {i}")

    # psum/pmax collectives over the stacked axis ≡ pairwise merge
    merged = bundle_merge(ref1, ref2)
    cms_all = jax.vmap(lambda s: cms_psum(s, "n"), axis_name="n")(out.cms)
    hll_all = jax.vmap(lambda s: hll_pmax(s, "n"), axis_name="n")(out.hll)
    ent_all = jax.vmap(lambda s: entropy_psum(s, "n"),
                       axis_name="n")(out.entropy)
    assert jnp.array_equal(cms_all.table[0], merged.cms.table)
    assert jnp.array_equal(hll_all.registers[0], merged.hll.registers)
    assert jnp.array_equal(ent_all.counts[0], merged.entropy.counts)


def test_window_digests_identical_on_fused_and_reference_paths():
    """Replay determinism across paths (ISSUE 10 satellite): the SAME
    recorded batch stream sealed into history windows must produce
    byte-identical window digests whether the bundle state came from the
    reference ops or the fused kernel — `replay --verify` cannot hold
    otherwise. Digests are the history plane's state-only content hash,
    so this pins bit-equality end to end, not just array equality."""
    from inspektor_gadget_tpu.history import window_digest
    from inspektor_gadget_tpu.history.window import SealedWindow
    from inspektor_gadget_tpu.ops.sketches import _bundle_update_pallas

    rng = np.random.default_rng(24)
    n = 256
    batches = [_streams(rng, n)[0] for _ in range(3)]
    mask = jnp.ones(n, bool)

    def seal(path):
        b = bundle_init(depth=2, log2_width=10, hll_p=8,
                        entropy_log2_width=8, k=8)
        for k in batches:
            if path == "fused":
                b = _bundle_update_pallas(b, k, k, k, mask, interpret=True)
            else:
                b = bundle_update(b, k, k, k, mask)
        win = SealedWindow(
            gadget="trace/parity", node="n0", run_id="r", window=1,
            start_ts=1.0, end_ts=2.0, events=int(b.events), drops=0,
            cms=np.asarray(b.cms.table, dtype=np.int32),
            hll=np.asarray(b.hll.registers, dtype=np.int32),
            ent=np.asarray(b.entropy.counts, dtype=np.float32),
            topk_keys=np.asarray(b.topk.keys),
            topk_counts=np.asarray(b.topk.counts, dtype=np.int64),
            slices={})
        return window_digest(win)

    assert seal("reference") == seal("fused")


# -- invertible heavy-key plane (ISSUE 15) -----------------------------------
# Merge-algebra property tier: the invertible lanes are pure integer adds,
# so every grouping/ordering of merges — pairwise host folds, device psum
# collectives, window-level adds — must produce identical state, and
# decode of that state must be exact whenever the distinct-key load fits
# pure buckets (<= inv_capacity). Beyond capacity the documented envelope
# is: recovered pairs stay exact, coverage degrades, complete=False.


def _inv_filled(rng, n_keys, rows=3, log2b=10, vocab_hi=1 << 22):
    """An InvSketch holding n_keys distinct keys with zipf-ish weights,
    plus the ground-truth {key: count} map."""
    import jax as _jax
    from inspektor_gadget_tpu.ops.invertible import inv_init, inv_update

    keys = rng.choice(np.arange(1, vocab_hi, dtype=np.uint32),
                      size=n_keys, replace=False)
    # cap at a value with few trailing zero bits: counts divisible by
    # 2^17+ are the documented decode blind spot, and a power-of-two
    # clip would manufacture exactly that pathology
    counts = rng.zipf(1.5, size=n_keys).clip(1, 100_000).astype(np.int64)
    step = _jax.jit(inv_update, donate_argnums=0)
    s = step(inv_init(rows, log2b), jnp.asarray(keys),
             jnp.asarray(counts.astype(np.int32)))
    return s, dict(zip(keys.tolist(), counts.tolist()))


def test_inv_merge_associative_and_commutative():
    from inspektor_gadget_tpu.ops.invertible import inv_merge

    rng = np.random.default_rng(31)
    states = [_inv_filled(rng, 100)[0] for _ in range(3)]
    a, b, c = states
    ab_c = inv_merge(inv_merge(a, b), c)
    a_bc = inv_merge(a, inv_merge(b, c))
    for lane in ("count", "keysum", "fpsum"):
        assert jnp.array_equal(getattr(ab_c, lane), getattr(a_bc, lane))
    ab, ba = inv_merge(a, b), inv_merge(b, a)
    for lane in ("count", "keysum", "fpsum"):
        assert jnp.array_equal(getattr(ab, lane), getattr(ba, lane))


def test_inv_psum_under_vmap_equals_pairwise_merge():
    """Device all-reduce (the cluster/fleet merge path) ≡ host pairwise
    merge — the two ways merged state is built may never diverge, or
    decode answers would depend on WHERE the merge ran."""
    from inspektor_gadget_tpu.ops.invertible import inv_merge, inv_psum

    rng = np.random.default_rng(32)
    a, _ = _inv_filled(rng, 80)
    b, _ = _inv_filled(rng, 80)
    stacked = jax.tree.map(lambda x, y: jnp.stack([x, y]), a, b)
    out = jax.vmap(lambda s: inv_psum(s, "nodes"),
                   axis_name="nodes")(stacked)
    want = inv_merge(a, b)
    for lane in ("count", "keysum", "fpsum"):
        assert jnp.array_equal(getattr(out, lane)[0], getattr(want, lane))
        assert jnp.array_equal(getattr(out, lane)[1], getattr(want, lane))


def test_inv_decode_exact_when_keys_fit_pure_buckets():
    """Under the documented capacity, decode recovers EVERY key with its
    EXACT total weight (odd and even totals alike — the host finisher's
    trailing-zero enumeration covers even pure buckets) and reports
    complete=True. Also across a merge: decode(merge(a,b)) == union."""
    from inspektor_gadget_tpu.ops.invertible import (inv_capacity,
                                                     inv_decode, inv_merge)

    rng = np.random.default_rng(33)
    rows, log2b = 3, 10
    cap = inv_capacity(rows, log2b)
    assert cap == 3 * 1024 // 4
    s, truth = _inv_filled(rng, cap // 2, rows=rows, log2b=log2b)
    dec = inv_decode(s)
    assert dec.complete and dec.residual_events == 0
    assert dict(dec.keys) == truth
    s2, truth2 = _inv_filled(rng, cap // 3, rows=rows, log2b=log2b)
    merged_truth = dict(truth)
    for k, c in truth2.items():
        merged_truth[k] = merged_truth.get(k, 0) + c
    dec2 = inv_decode(inv_merge(s, s2))
    assert dec2.complete
    assert dict(dec2.keys) == merged_truth


def test_inv_decode_device_loop_matches_host_only_decode():
    """The jittable fixed-iteration device loop + host finisher must
    answer exactly like the pure-numpy peel over the same state."""
    from inspektor_gadget_tpu.ops.invertible import inv_decode

    rng = np.random.default_rng(34)
    s, truth = _inv_filled(rng, 300)
    via_device = inv_decode(s)                      # jnp leaves → device loop
    host_only = inv_decode((np.asarray(s.count), np.asarray(s.keysum),
                            np.asarray(s.fpsum)))   # numpy → host peel only
    assert dict(via_device.keys) == dict(host_only.keys) == truth
    assert via_device.complete and host_only.complete


def test_inv_decode_error_envelope_on_zipf_overload():
    """Past capacity the decode is PARTIAL, never wrong: every recovered
    pair must match ground truth exactly, completeness is reported
    False, and the undecoded mass is accounted in residual_events."""
    from inspektor_gadget_tpu.ops.invertible import (inv_capacity,
                                                     inv_decode)

    rng = np.random.default_rng(35)
    rows, log2b = 3, 8
    cap = inv_capacity(rows, log2b)
    s, truth = _inv_filled(rng, cap * 4, rows=rows, log2b=log2b)
    dec = inv_decode(s)
    assert not dec.complete
    for k, c in dec.keys:
        assert truth.get(k) == c, (k, c)
    total = sum(truth.values())
    recovered_mass = sum(c for _, c in dec.keys)
    assert recovered_mass + dec.residual_events == total


def test_fused_kernel_parity_with_invertible_planes():
    """Interpret-mode fused kernel vs the reference composition with the
    invertible planes ON: every bundle leaf — the new count/keysum/fpsum
    lanes included — is bit-identical, over ragged masks and a second
    batch on live state."""
    from inspektor_gadget_tpu.ops.sketches import _bundle_update_pallas

    rng = np.random.default_rng(36)
    leaves = _BUNDLE_LEAVES + ("inv.count", "inv.keysum", "inv.fpsum",
                               "topk.overflow")
    for depth, log2w, entw, p, inv_rows, inv_lb, n, valid in (
            (4, 10, 8, 8, 3, 9, 256, 256),
            (2, 12, 10, 7, 2, 12, 512, 501),):
        b0 = bundle_init(depth=depth, log2_width=log2w, hll_p=p,
                         entropy_log2_width=entw, k=16,
                         inv_rows=inv_rows, inv_log2_buckets=inv_lb)
        hh, distinct, dist = _streams(rng, n)
        mask = jnp.asarray(np.arange(n) < valid)
        ref = bundle_update(b0, hh, distinct, dist, mask, jnp.float32(1))
        fused = _bundle_update_pallas(b0, hh, distinct, dist, mask,
                                      jnp.float32(1), interpret=True)
        for name in leaves:
            assert np.array_equal(_leaf(ref, name), _leaf(fused, name)), \
                (name, depth, inv_rows)
        hh2, d2, dd2 = _streams(rng, n)
        ref2 = bundle_update(ref, hh2, d2, dd2, mask)
        fused2 = _bundle_update_pallas(fused, hh2, d2, dd2, mask,
                                       interpret=True)
        for name in leaves:
            assert np.array_equal(_leaf(ref2, name), _leaf(fused2, name)), \
                ("second batch", name)


def test_candidate_overflow_flag_flips_exactly_at_overflow():
    """The approx flag (ISSUE 15 satellite): k distinct candidate keys
    leave it 0 — the re-rank is exact; the (k+1)-th distinct key flips
    it to 1, on update AND merge paths, and psum/merge never resets it."""
    from inspektor_gadget_tpu.ops.sketches import decode_digest, bundle_digest

    k = 8
    n = 256
    mask = jnp.ones(n, bool)

    def feed(b, vocab):
        keys = jnp.asarray((np.arange(n) % vocab + 1).astype(np.uint32))
        return bundle_update(b, keys, keys, keys, mask)

    b = bundle_init(depth=2, log2_width=10, hll_p=8,
                    entropy_log2_width=8, k=k)
    b = feed(b, k)                       # exactly k distinct
    assert int(b.topk.overflow) == 0
    assert decode_digest(bundle_digest(b))[4] is False
    b = feed(b, k + 1)                   # the (k+1)-th distinct key
    assert int(b.topk.overflow) == 1
    assert decode_digest(bundle_digest(b))[4] is True
    # merge paths: union overflow + latched inputs
    a1 = feed(bundle_init(depth=2, log2_width=10, hll_p=8,
                          entropy_log2_width=8, k=k), k)
    a2keys = jnp.asarray((np.arange(n) % k + 100).astype(np.uint32))
    a2 = bundle_update(bundle_init(depth=2, log2_width=10, hll_p=8,
                                   entropy_log2_width=8, k=k),
                       a2keys, a2keys, a2keys, mask)
    assert int(a1.topk.overflow) == 0 and int(a2.topk.overflow) == 0
    m = bundle_merge(a1, a2)             # union is 2k distinct > k
    assert int(m.topk.overflow) == 1
    m2 = bundle_merge(m, bundle_init(depth=2, log2_width=10, hll_p=8,
                                     entropy_log2_width=8, k=k))
    assert int(m2.topk.overflow) == 1    # latched through further merges


def test_window_digest_invertible_plane_conditional():
    """Digest discipline: a window without the invertible arrays hashes
    exactly as before the plane existed (the fields never enter the
    doc), and adding the arrays changes — removing them restores — the
    digest, so plane-off replay `--verify` stays green."""
    from inspektor_gadget_tpu.history import window_digest
    from inspektor_gadget_tpu.history.window import (SealedWindow,
                                                     decode_window,
                                                     encode_window)

    base = dict(
        gadget="t", node="n", run_id="r", window=1, start_ts=1.0,
        end_ts=2.0, events=10, drops=0,
        cms=np.ones((2, 8), np.int32), hll=np.zeros(16, np.int32),
        ent=np.zeros(8, np.float32),
        topk_keys=np.array([5], np.uint32),
        topk_counts=np.array([10], np.int64), slices={})
    plain = SealedWindow(**base)
    with_inv = SealedWindow(**base,
                            inv_count=np.ones((2, 8), np.int32),
                            inv_keysum=np.ones((2, 8), np.uint32),
                            inv_fpsum=np.ones((2, 8), np.uint32))
    assert window_digest(plain) != window_digest(with_inv)
    stripped = SealedWindow(**base)
    assert window_digest(plain) == window_digest(stripped)
    # codec roundtrip preserves the plane bit-for-bit
    h, payload = encode_window(with_inv)
    back = decode_window(h, payload)
    assert np.array_equal(back.inv_count, with_inv.inv_count)
    assert np.array_equal(back.inv_keysum, with_inv.inv_keysum)
    assert np.array_equal(back.inv_fpsum, with_inv.inv_fpsum)
    assert window_digest(back) == window_digest(with_inv)


def test_merge_windows_inv_plane_fold_and_refusal():
    """Range-fold semantics: windows all carrying the plane fold into
    decodable merged state (decode == union of per-window streams);
    one window WITHOUT the plane disables decode for the range with a
    loud note instead of decoding partial coverage."""
    import jax as _jax
    from inspektor_gadget_tpu.history import merge_windows
    from inspektor_gadget_tpu.history.window import SealedWindow
    from inspektor_gadget_tpu.ops.invertible import (inv_decode, inv_init,
                                                     inv_update)

    step = _jax.jit(inv_update, donate_argnums=0)
    rng = np.random.default_rng(37)

    def window_of(i, keys, counts, with_inv=True):
        s = step(inv_init(2, 8), jnp.asarray(keys),
                 jnp.asarray(counts.astype(np.int32)))
        kw = {}
        if with_inv:
            kw = dict(inv_count=np.asarray(s.count),
                      inv_keysum=np.asarray(s.keysum),
                      inv_fpsum=np.asarray(s.fpsum))
        return SealedWindow(
            gadget="t", node="n", run_id="r", window=i,
            start_ts=float(i), end_ts=float(i + 1),
            events=int(counts.sum()), drops=0,
            cms=np.zeros((2, 8), np.int32), hll=np.zeros(16, np.int32),
            ent=np.zeros(8, np.float32),
            topk_keys=np.zeros(4, np.uint32),
            topk_counts=np.zeros(4, np.int64), slices={}, **kw)

    k1 = rng.choice(np.arange(1, 1000, dtype=np.uint32), 40, replace=False)
    c1 = rng.integers(1, 50, 40).astype(np.int64)
    k2 = rng.choice(np.arange(1000, 2000, dtype=np.uint32), 30,
                    replace=False)
    c2 = rng.integers(1, 50, 30).astype(np.int64)
    w1, w2 = window_of(1, k1, c1), window_of(2, k2, c2)
    merged = merge_windows([w1, w2])
    truth = dict(zip(k1.tolist(), c1.tolist()))
    truth.update(zip(k2.tolist(), c2.tolist()))
    assert dict(merged.heavy_flows()) == truth
    # one plane-less window → decode disabled, loudly
    merged2 = merge_windows([w1, window_of(3, k2, c2, with_inv=False)])
    assert merged2.inv_count is None
    assert merged2.heavy_flows() == []
    assert any("heavy-flow decode disabled" in s for s in merged2.skipped)


def test_windowed_cms_merge_and_jit():
    import jax as _jax
    from inspektor_gadget_tpu.ops.window import (
        wcms_init, wcms_merge, wcms_query, wcms_update)

    a = wcms_init(n_slots=2, depth=4, log2_width=10)
    b = wcms_init(n_slots=2, depth=4, log2_width=10)
    keys = jnp.array([5, 5, 6], dtype=jnp.uint32)
    upd = _jax.jit(wcms_update)
    a = upd(a, keys)
    b = upd(b, keys)
    m = wcms_merge(a, b)
    q = wcms_query(m, jnp.array([5, 6], dtype=jnp.uint32))
    assert q[0] == 4 and q[1] == 2


def test_fused_kernel_parity_with_quantile_plane():
    """Interpret-mode fused kernel vs the reference composition with the
    DDSketch quantile plane ON: every bundle leaf — counts/zeros/total
    value lanes included — is bit-identical, over ragged masks, a second
    batch on live state, and with the invertible planes riding along."""
    from inspektor_gadget_tpu.ops.sketches import _bundle_update_pallas

    rng = np.random.default_rng(40)
    for depth, log2w, entw, p, inv_rows, n, valid in (
            (4, 10, 8, 8, 0, 256, 256),
            (2, 12, 10, 7, 2, 512, 501),):    # ragged + inv planes too
        leaves = _BUNDLE_LEAVES + ("quantiles.counts", "quantiles.zeros",
                                   "quantiles.total")
        if inv_rows:
            leaves += ("inv.count", "inv.keysum", "inv.fpsum")
        b0 = bundle_init(depth=depth, log2_width=log2w, hll_p=p,
                         entropy_log2_width=entw, k=16,
                         inv_rows=inv_rows, inv_log2_buckets=10,
                         quantiles=True, quantile_buckets=2048)
        hh, distinct, dist = _streams(rng, n)
        vals = jnp.asarray(rng.lognormal(np.log(50_000.0), 1.2, n)
                           .astype(np.float32).astype(np.uint32))
        vals = vals.at[:5].set(0)            # exercise the zero bucket
        mask = jnp.asarray(np.arange(n) < valid)
        ref = bundle_update(b0, hh, distinct, dist, mask, jnp.float32(1),
                            values=vals)
        fused = _bundle_update_pallas(b0, hh, distinct, dist, mask,
                                      jnp.float32(1), values=vals,
                                      interpret=True)
        for name in leaves:
            assert np.array_equal(_leaf(ref, name), _leaf(fused, name)), \
                (name, depth, inv_rows)
        hh2, d2, dd2 = _streams(rng, n)
        vals2 = jnp.asarray(rng.integers(0, 1 << 20, n, dtype=np.uint32))
        ref2 = bundle_update(ref, hh2, d2, dd2, mask, values=vals2)
        fused2 = _bundle_update_pallas(fused, hh2, d2, dd2, mask,
                                       values=vals2, interpret=True)
        for name in leaves:
            assert np.array_equal(_leaf(ref2, name), _leaf(fused2, name)), \
                ("second batch", name)


def test_bundle_quantile_plane_matches_standalone_sketch():
    """The bundle's value-lane fold must produce the exact DDSketch the
    standalone dd_update produces over the same masked values — the
    bundle plane is the same sketch, just riding the fused step."""
    from inspektor_gadget_tpu.ops import dd_init, dd_update

    rng = np.random.default_rng(41)
    n = 512
    b = bundle_init(depth=2, log2_width=10, hll_p=8,
                    entropy_log2_width=6, k=8, quantiles=True,
                    quantile_buckets=1024, quantile_alpha=0.02)
    hh, distinct, dist = _streams(rng, n)
    vals = rng.integers(0, 1 << 24, n, dtype=np.uint32)
    vals[:17] = 0
    mask = np.arange(n) < 400
    got = bundle_update(b, hh, distinct, dist, jnp.asarray(mask),
                        values=jnp.asarray(vals))
    want = dd_update(dd_init(alpha=0.02, n_buckets=1024, min_value=1.0),
                     jnp.asarray(vals.astype(np.float32)),
                     jnp.asarray(mask))
    np.testing.assert_array_equal(np.asarray(got.quantiles.counts),
                                  np.asarray(want.counts))
    assert int(got.quantiles.zeros) == int(want.zeros) == 17
    assert int(got.quantiles.total) == int(want.total) == 400
    # plane-off bundle: quantiles stays None and values= is refused
    off = bundle_init(depth=2, log2_width=10, hll_p=8,
                      entropy_log2_width=6, k=8)
    assert off.quantiles is None
    out = bundle_update(off, hh, distinct, dist, jnp.asarray(mask))
    assert out.quantiles is None


def test_window_digest_quantile_plane_conditional():
    """Same digest discipline as the invertible plane: a window without
    the quantile lanes hashes exactly as before the plane existed, the
    lanes change the digest when present, and the codec roundtrips them
    bit-for-bit."""
    from inspektor_gadget_tpu.history import window_digest
    from inspektor_gadget_tpu.history.window import (SealedWindow,
                                                     decode_window,
                                                     encode_window)

    base = dict(
        gadget="t", node="n", run_id="r", window=1, start_ts=1.0,
        end_ts=2.0, events=10, drops=0,
        cms=np.ones((2, 8), np.int32), hll=np.zeros(16, np.int32),
        ent=np.zeros(8, np.float32),
        topk_keys=np.array([5], np.uint32),
        topk_counts=np.array([10], np.int64), slices={})
    plain = SealedWindow(**base)
    with_qt = SealedWindow(**base,
                           qt_counts=np.arange(32, dtype=np.int64),
                           qt_zeros=3, qt_total=499, qt_alpha=0.02,
                           qt_min_value=1.0)
    assert window_digest(plain) != window_digest(with_qt)
    assert window_digest(plain) == window_digest(SealedWindow(**base))
    h, payload = encode_window(with_qt)
    back = decode_window(h, payload)
    assert np.array_equal(back.qt_counts, with_qt.qt_counts)
    assert back.qt_zeros == 3 and back.qt_total == 499
    assert back.qt_alpha == 0.02 and back.qt_min_value == 1.0
    assert window_digest(back) == window_digest(with_qt)
    # plane-off window: no qt keys on the wire at all
    h2, _ = encode_window(plain)
    assert not any(k.startswith("qt_") for k in h2)


def test_merge_windows_qt_plane_fold_and_refusal():
    """Range-fold semantics for the quantile plane: matching-geometry
    windows fold into lanes whose quantile read equals the ground-truth
    combined stream; a plane-less window or a different alpha drops the
    plane from the answer WITH a note — a mixed-base fold would render
    confident-looking but wrong percentiles."""
    import jax as _jax
    from inspektor_gadget_tpu.history import merge_windows
    from inspektor_gadget_tpu.history.window import SealedWindow
    from inspektor_gadget_tpu.ops import dd_init, dd_update

    step = _jax.jit(dd_update, donate_argnums=0)
    rng = np.random.default_rng(42)

    def window_of(i, vals, with_qt=True, alpha=0.01):
        kw = {}
        if with_qt:
            s = step(dd_init(alpha=alpha, n_buckets=1024, min_value=1.0),
                     jnp.asarray(vals))
            kw = dict(qt_counts=np.asarray(s.counts),
                      qt_zeros=int(s.zeros), qt_total=int(s.total),
                      qt_alpha=alpha, qt_min_value=1.0)
        return SealedWindow(
            gadget="t", node="n", run_id="r", window=i,
            start_ts=float(i), end_ts=float(i + 1),
            events=len(vals), drops=0,
            cms=np.zeros((2, 8), np.int32), hll=np.zeros(16, np.int32),
            ent=np.zeros(8, np.float32),
            topk_keys=np.zeros(4, np.uint32),
            topk_counts=np.zeros(4, np.int64), slices={}, **kw)

    v1 = rng.lognormal(np.log(30_000.0), 0.7, 600).astype(np.float32)
    v2 = rng.lognormal(np.log(900_000.0), 0.7, 400).astype(np.float32)
    w1, w2 = window_of(1, v1), window_of(2, v2)
    merged = merge_windows([w1, w2])
    assert merged.qt_total == 1000 and merged.qt_zeros == 0
    both = np.concatenate([v1, v2])
    for q in (0.5, 0.9, 0.99):
        est = float(merged.quantile(q))
        true = float(np.quantile(both, q))
        assert abs(est - true) / true < 0.03, (q, est, true)
    # the quantile_answer block is wire-shaped and self-describing
    ans = merged.quantile_answer()
    assert ans["total"] == 1000 and ans["alpha"] == 0.01
    assert set(ans) >= {"p50", "p90", "p99", "p999"}
    # histogram over the merged lanes conserves positive mass
    hist = merged.histogram_log2()
    assert int(hist.sum()) == merged.qt_total - merged.qt_zeros
    # a plane-less window in the range → quantiles disabled, loudly
    m2 = merge_windows([w1, window_of(3, v2, with_qt=False)])
    assert m2.qt_counts is None and np.isnan(m2.quantile(0.5))
    assert m2.quantile_answer() is None
    assert any("latency quantiles disabled" in s for s in m2.skipped)
    # a different log base (alpha) → refusal, not a silent mixed fold
    m3 = merge_windows([w1, window_of(4, v2, alpha=0.05)])
    assert m3.qt_counts is None
    assert any("quantile geometry" in s for s in m3.skipped)
    # order matters not: plane-less FIRST also disables with a note
    m4 = merge_windows([window_of(5, v1, with_qt=False), w2])
    assert m4.qt_counts is None
    assert any("earlier window lacked" in s for s in m4.skipped)
