"""The seccomp recorder with the anomaly scorer on, served (ISSUE 31).

`advise seccomp-profile` + `anomaly true` through `LocalRuntime.run_gadget`
at a small size, held to `chipbench/reference_scorer.py` on every summary:
scores within its tolerance (and three planted faults outside it),
per-container histograms and syscall sets exact, the emitted profile the
one the per-event loop it replaces would have written. Beside it the pieces
on their own: the grouping, the recorder's bitmap, the `[slots, dim]`
array against the `dict` of vectors, `anomaly_step` against the eager pair,
and the slots doubling with one compile.
"""

from __future__ import annotations

import json
import sys
from collections import defaultdict
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import inspektor_gadget_tpu.all_gadgets  # noqa: F401
from inspektor_gadget_tpu.gadgets import GadgetContext, get
from inspektor_gadget_tpu.gadgets.advise.seccomp_profile import (
    SYSCALL_BITS, generate_oci_seccomp_profile)
from inspektor_gadget_tpu.models import autoencoder as ae
from inspektor_gadget_tpu.operators import tpusketch
from inspektor_gadget_tpu.operators.operators import get as get_op
from inspektor_gadget_tpu.sources.batch import EventBatch
from inspektor_gadget_tpu.telemetry import snapshot
from inspektor_gadget_tpu.telemetry.pipeline import (DISTS_STAGE,
                                                     RECORD_STAGE)
from inspektor_gadget_tpu.utils.compile_cache import ensure_compile_cache
from inspektor_gadget_tpu.utils.grouping import SlotTable, group_codes
from inspektor_gadget_tpu.utils.syscalls import syscall_name

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [p for p in (str(ROOT),) if p not in sys.path]

import chip_smoke  # noqa: E402

ref = chip_smoke.reference_scorer()

# the smoke's CPU size: batches of 2,048, dim 2^8 (an AE 256-256-64)
SIZE = chip_smoke.SIZES["cpu"]
G = 'gadget="advise/seccomp-profile"'


# -- the grouping -----------------------------------------------------------

@pytest.mark.parametrize("case", ["close", "spread", "one", "mixed"])
def test_group_codes_is_unique_with_its_inverse(case):
    rng = np.random.default_rng(31)
    ids = {
        "close": 4026531840 + rng.integers(0, 64, 5000),
        "spread": rng.integers(0, 1 << 62, 300),
        "one": np.full(17, 4026531999),
        "mixed": np.concatenate([rng.integers(5, 9, 100),
                                 rng.integers(1 << 40, 1 << 41, 3)]),
    }[case].astype(np.uint64)
    vals, code = group_codes(ids)
    want_vals, want_code = np.unique(ids, return_inverse=True)
    assert vals.dtype == ids.dtype
    assert np.array_equal(vals, want_vals) and np.array_equal(code, want_code)


def test_slots_are_given_in_the_order_a_dict_of_uniques_would():
    rng = np.random.default_rng(32)
    table, plain = SlotTable(), {}
    for _ in range(20):
        ids = rng.integers(100, 140, rng.integers(1, 50)).astype(np.uint64)
        for v in np.unique(ids).tolist():
            plain.setdefault(v, len(plain))
        assert table.slots_of(ids).tolist() == [plain[v] for v in ids.tolist()]
    assert table.ids() == list(plain) and len(table) == len(plain)


# -- the recorder -----------------------------------------------------------

def batch_of(mntns, keys, aux2) -> EventBatch:
    n = len(mntns)
    b = EventBatch.alloc(max(n, 1), with_comm=False)
    b.cols["mntns"][:n], b.cols["key_hash"][:n] = mntns, keys
    b.cols["aux2"][:n], b.cols["ts"][:n] = aux2, 1
    b.count = n
    return b


@pytest.mark.parametrize("native", [False, True])
def test_the_bitmap_holds_what_the_per_event_loop_held(native):
    """The loop this replaces, written out: three `int()`s and a
    `set.add` an event. Native numbers sit in aux2's high word, and one
    past the bitmap's columns is kept beside it."""
    rng = np.random.default_rng(33)
    gadget = get("advise", "seccomp-profile").new_instance(
        GadgetContext(get("advise", "seccomp-profile")))
    gadget._is_native = native
    plain: dict[int, set[int]] = defaultdict(set)
    for i in range(6):
        n = 700
        # 70 containers: past the 64 rows the bitmap starts with
        mntns = (4026531840 + rng.integers(0, 10 + 12 * i, n)).astype(
            np.uint64)
        aux2 = rng.integers(0, 1 << 16, n).astype(np.uint64)
        if native:
            nr = rng.integers(0, 460, n).astype(np.uint64)
            nr[::97] = 0x40000000 + 5          # an x32 number: past 512
            aux2 |= nr << np.uint64(32)
        gadget.process_batch(batch_of(mntns, mntns, aux2))
        for j in range(n):
            a = int(aux2[j])
            plain[int(mntns[j])].add((a >> 32) if native else a % 335)
    assert gadget.syscall_sets() == dict(plain)
    assert len(gadget._bitmap) == 128 and gadget._bitmap.shape[1] == SYSCALL_BITS
    assert bool(gadget._wide) == native


# -- the per-container distributions ----------------------------------------

@pytest.fixture
def _release_instances():
    """Instances built outside a gadget run: out of the live table, their
    stagers drained (as tests/test_step_rows.py does)."""
    before = set(tpusketch._live)
    yield
    with tpusketch._live_mu:
        fresh = [rid for rid in list(tpusketch._live) if rid not in before]
        insts = [tpusketch._live.pop(rid) for rid in fresh]
    for inst in insts:
        if inst._stager is not None:
            inst._stager.drain()
        inst._stats.unregister()
        inst._pstats.unregister()


def make_instance(tmp_path, **params):
    desc = get("advise", "seccomp-profile")
    ctx = GadgetContext(desc)
    ctx.gadget_params.set("batch-size", "2048")
    p = get_op("tpusketch").instance_params().to_params()
    for k, v in {"enable": "true", "log2-width": "8", "hll-p": "6",
                 "entropy-log2-width": "6", "topk": "16", "anomaly": "true",
                 "harvest-interval": "1h", **params}.items():
        p.set(k, v)
    return get_op("tpusketch").instantiate(ctx, None, p)


def container_batch(rng, containers: int, n: int = 1500) -> EventBatch:
    mntns = (4026531840 + rng.integers(0, containers, n)).astype(np.uint64)
    keys = rng.integers(1, 1 << 40, n).astype(np.uint64)
    return batch_of(mntns, keys, keys)


def test_the_array_holds_what_the_dict_of_vectors_held(
        tmp_path, _release_instances):
    """The code this replaces, written out: a boolean mask and an
    `np.add.at` per container of `np.unique(mntns)`, into a `dict`."""
    rng = np.random.default_rng(34)
    inst = make_instance(tmp_path)
    dim = 64
    plain: dict[int, np.ndarray] = {}
    for containers in (3, 40, 64, 70, 70, 130):
        b = container_batch(rng, containers)
        inst.enrich_batch(b)
        mntns, keys = b.cols["mntns"][:b.count], b.cols["key_hash"][:b.count]
        buckets = (keys % np.uint64(dim)).astype(np.int64)
        for ns in np.unique(mntns):
            vec = plain.setdefault(int(ns), np.zeros(dim, np.float32))
            np.add.at(vec, buckets[mntns == ns], 1.0)
    ids, counts = inst.container_distributions()
    assert ids == list(plain)                      # the same rows, in order
    assert counts.dtype == np.float32
    assert np.array_equal(counts, np.stack(list(plain.values())))
    assert len(inst._container_counts) == 256      # 64 -> 128 -> 256


def compiles() -> float:
    return snapshot()["ig_jax_backend_compiles_total"]


def test_a_65th_container_doubles_the_slots_and_compiles_once(
        tmp_path, _release_instances):
    ensure_compile_cache()
    rng = np.random.default_rng(35)
    inst = make_instance(tmp_path)
    inst.pre_gadget_run()
    steps0 = int(inst.scorer.steps)
    assert steps0 == 0                   # priming stepped a copy
    inst.enrich_batch(container_batch(rng, 64))
    first = inst.harvest()               # the digest compiles here, once
    assert len(first.anomaly) == 64
    assert first.pipeline["anomaly"] == {"steps": 1, "containers": 64,
                                         "slots": 64, "primed_slots": 64}
    base = compiles()
    inst.enrich_batch(container_batch(rng, 64))
    assert len(inst.harvest().anomaly) == 64
    assert compiles() == base            # primed at 64 slots: none
    inst.enrich_batch(container_batch(rng, 65, n=4000))
    s65 = inst.harvest()
    assert len(s65.anomaly) == 65
    assert s65.pipeline["anomaly"]["slots"] == 128
    assert compiles() == base + 1        # the step at 128 rows
    inst.enrich_batch(container_batch(rng, 66, n=4000))
    s66 = inst.harvest()
    assert len(s66.anomaly) == 66 and s66.pipeline["anomaly"]["slots"] == 128
    assert compiles() == base + 1
    assert int(inst.scorer.steps) == 4
    assert snapshot()[f"ig_tpusketch_anomaly_slots{{{G}}}"] == 128.0


# -- the step ---------------------------------------------------------------

@pytest.mark.parametrize("rows, slots", [(3, 64), (64, 64), (70, 128)])
def test_anomaly_step_gives_the_eager_pairs_scores(rows, slots):
    """One jitted, padded, masked step against `ae_train_step` and
    `ae_score` called eagerly on the real rows alone, twice over. The
    mathematics is the same and filler rows add exact zeros to the
    gradient. What differs is rounding: called eagerly every bfloat16
    operation rounds its result, while inside one program XLA carries a
    chain of element-wise operations (bias, gelu) in float32 and rounds
    once, so a score moves by a few parts in a thousand (3e-3 read here):
    half of what the reference's tolerance allows the program."""
    rng = np.random.default_rng(36)
    cfg = ae.AEConfig(input_dim=256, hidden_dim=256, latent_dim=64)
    counts = np.zeros((slots, 256), np.float32)
    counts[:rows] = rng.poisson(3.0, (rows, 256))
    mask = np.zeros(slots, np.float32)
    mask[:rows] = 1.0
    eager, jitted = ae.ae_init(cfg), ae.ae_init(cfg)
    for _ in range(2):
        x = ae.normalize_counts(jnp.asarray(counts[:rows]))
        eager, _loss = ae.ae_train_step(eager, x)
        want = np.asarray(ae.ae_score(eager, x))
        jitted, scores = ae.anomaly_step(jitted, counts, mask)
        np.testing.assert_allclose(np.asarray(scores)[:rows], want,
                                   rtol=1e-2)
        counts[:rows] += rng.poisson(1.0, (rows, 256))
    assert int(jitted.steps) == int(eager.steps) == 2
    # Adam moves a weight by about 1e-3 a step whatever its gradient's
    # size, so where a gradient is all rounding the two may part by a
    # twentieth of a step
    for a, b in zip(jax.tree.leaves(jitted.params),
                    jax.tree.leaves(eager.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


# -- the served run against the reference -----------------------------------

@pytest.fixture(scope="module")
def served():
    return chip_smoke.anomaly_run(SIZE, 3100031)


def test_served_scores_meet_the_replay_on_every_summary(served):
    """`TOLERANCE`'s reason is in chipbench/reference_scorer.py: bfloat16
    matrix products and activations against float32 at "highest"."""
    # every summary judged, not the smoke's nine
    r = ref.compare(served["recorded"], start_weights(), 256)
    assert r["score_keys_equal"]
    assert r["scores_compared"] >= 64 * 30
    assert r["score_gap"] <= ref.TOLERANCE, r


def start_weights() -> dict:
    return jax.tree.map(np.asarray, ae.ae_init(ae.AEConfig(
        input_dim=256, hidden_dim=256, latent_dim=64)).params)


def test_served_histograms_and_syscall_sets_are_exact(served):
    r = served["readings"]
    assert r["histograms_exact"] is True and r["profile_exact"] is True
    assert r["harvests"] == served["pipeline"]["anomaly"]["steps"]


def test_the_emitted_profile_is_the_per_event_loops(served):
    rec = served["recorded"]
    plain: dict[int, set[int]] = defaultdict(set)
    for mntns, aux2 in zip(rec.mntns, rec.aux2):
        for ns, a in zip(mntns.tolist(), aux2.tolist()):
            plain[ns].add(a % 335)
    profiles = {str(ns): generate_oci_seccomp_profile(
        {syscall_name(nr) for nr in nrs}) for ns, nrs in sorted(plain.items())}
    assert served["emitted"] == (json.dumps(profiles, indent=2)
                                 + "\n").encode()


def test_the_run_names_its_stages_and_counters(served):
    pipe = served["pipeline"]
    assert pipe["anomaly"]["containers"] == 64
    assert pipe["anomaly"]["slots"] == 64
    turn = pipe["turn"]
    for stage in (RECORD_STAGE, DISTS_STAGE):
        assert turn["stages"][stage] > 0.0, stage
    assert 0.0 < turn["anomaly_score_s"] < turn["stages"]["tpusketch_harvest"]
    snap = snapshot()
    assert snap[f'ig_tpusketch_anomaly_steps_total{{{G},model="ae"}}'] >= (
        pipe["anomaly"]["steps"])
    assert snap[f"ig_tpusketch_anomaly_slots{{{G}}}"] == 0.0   # torn down
    for stage in (RECORD_STAGE, DISTS_STAGE, "anomaly_score"):
        assert snap[f'ig_pipeline_turn_seconds_total{{stage="{stage}"}}'] > 0


@pytest.mark.parametrize("fault", chip_smoke.SCORER_FAULTS)
def test_a_planted_fault_reads_over_the_tolerance(fault):
    """A training step skipped, the scores taken before the step, the
    parameters rounded to bfloat16: each must fail the comparison."""
    run = chip_smoke.anomaly_run(SIZE, 3100032, fault)
    r = run["readings"]
    assert r["histograms_exact"] and r["profile_exact"]
    assert r["score_gap"] > ref.TOLERANCE, (fault, r)
