"""A dense node, served (ISSUE 34): `advise seccomp-profile` with the
anomaly scorer on and `containers 1024` on the synthetic source, through
`LocalRuntime.run_gadget` at a small geometry.

Held here: the `containers` parameter (validated against upstream's cap,
and at its default of 64 the stream the source emitted before it existed,
byte for byte); the scores against `chipbench/reference_scorer.py`, the
histograms and syscall sets exact; every sealed window's per-container
slices against a plain reference written out below (a dict a container:
exact event count, exact distinct keys, exact top 32); at
`history-max-slices 256` exactly the slices over the cap dropped and
counted; and the scorer's program primed at the slots the run reaches, so
that nothing compiles after the source starts.
"""

from __future__ import annotations

import hashlib
import sys
import time
from collections import Counter
from pathlib import Path

import jax
import numpy as np
import pytest

import inspektor_gadget_tpu.all_gadgets  # noqa: F401
from inspektor_gadget_tpu.gadgets import get
from inspektor_gadget_tpu.gadgets.source_gadget import MAX_CONTAINERS_PER_NODE
from inspektor_gadget_tpu.history.window import (SLICE_HH_K, SLICE_HLL_P,
                                                 slice_hll_estimate)
from inspektor_gadget_tpu.models import autoencoder as ae
from inspektor_gadget_tpu.operators import tpusketch
from inspektor_gadget_tpu.ops import fold64_to_32
from inspektor_gadget_tpu.params import ParamError
from inspektor_gadget_tpu.sources.bridge import (SRC_SYNTH_EXEC,
                                                 SRC_SYNTH_TCP, NativeCapture)
from inspektor_gadget_tpu.sources.synthetic import PySyntheticSource
from inspektor_gadget_tpu.telemetry import snapshot
from inspektor_gadget_tpu.telemetry.pipeline import DISTS_STAGE
from inspektor_gadget_tpu.utils.compile_cache import ensure_compile_cache

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [p for p in (str(ROOT),) if p not in sys.path]

import chip_smoke  # noqa: E402

ref = chip_smoke.reference_scorer()

SIZE = chip_smoke.SIZES["cpu"]      # batches of 2,048, an AE 256-256-64
CONTAINERS = chip_smoke.DENSE["containers"]
MNTNS_BASE = 4026531840
G = 'gadget="advise/seccomp-profile"'
SLICES = "ig_history_slices_total"

# -- the source's parameter ---------------------------------------------------

# sha256 over the columns of the first 4,096 events, recorded from the
# source as it was before it had the parameter (commit 52bad71): through
# `generate` (key_hash, mntns, pid, uid) and through the producer thread
# and `pop` (every column but the clock's)
RECORDED = {
    "exec": (SRC_SYNTH_EXEC, 7, 21440, 1.2,
             "dc6f11d0fc18037eaaed5208d74e50735f0b2eefffaa30f2cd708b45391807f5",
             "41668eb7b18766cae91efb82f01664653230741ad5e5c4bf1b464c45ef927c68",
             [8, 1, 27, 10, 24, 21, 21, 1]),
    "tcp": (SRC_SYNTH_TCP, 2**31 + 5, 1000, 0.99,
            "4b52aa12c69da011a9fd4109e962964f9c329a2ae589fd4600415c131fae488d",
            "eea75fe68a502fbd104ce9a7fd8d969118c1ea53d1bbea58d51afc9eaa762ecf",
            [20, 25, 25, 3, 26, 46, 3, 47]),
}
POPPED = ("key_hash", "mntns", "pid", "ppid", "uid", "kind", "aux1", "aux2")


def digest(cols: dict, names: tuple) -> str:
    h = hashlib.sha256()
    for c in names:
        h.update(np.ascontiguousarray(cols[c][:4096]).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("case", sorted(RECORDED))
@pytest.mark.parametrize("explicit", [False, True])
def test_at_64_containers_the_stream_is_the_recorded_one(case, explicit):
    kind, seed, vocab, zipf, generated, popped, head = RECORDED[case]
    kw = {"containers": 64} if explicit else {}
    src = NativeCapture(kind, seed=seed, vocab=vocab, zipf_s=zipf,
                        batch_size=4096, **kw)
    b = src.generate(4096)
    src.close()
    assert (b.cols["mntns"][:8] - MNTNS_BASE).tolist() == head
    assert digest(b.cols, ("key_hash", "mntns", "pid", "uid")) == generated
    src = NativeCapture(kind, seed=seed, rate=40000.0, vocab=vocab,
                        zipf_s=zipf, batch_size=4096, **kw)
    src.start()
    got, n = {c: [] for c in POPPED}, 0
    deadline = time.monotonic() + 30
    while n < 4096 and time.monotonic() < deadline:
        b = src.pop()
        if not b.count:
            time.sleep(0.005)
            continue
        for c in POPPED:
            got[c].append(b.cols[c][:b.count].copy())
        n += b.count
    src.stop()
    assert src.drops() == 0 and n >= 4096
    src.close()
    assert digest({c: np.concatenate(got[c]) for c in POPPED},
                  POPPED) == popped


@pytest.mark.parametrize("containers", [1, 100, 1024])
def test_a_containers_namespace_is_its_keys_rank_modulo_containers(
        containers):
    src = NativeCapture(SRC_SYNTH_EXEC, seed=11, vocab=5 * containers + 3,
                        zipf_s=0.9, batch_size=8192, containers=containers)
    b = src.generate(8192)
    names = src.vocab_lookup_batch(b.cols["key_hash"][:b.count])
    src.close()
    rank = np.array([int(n.removeprefix("proc-")) for n in names])
    assert np.array_equal(b.cols["mntns"][:b.count] - MNTNS_BASE,
                          rank % containers)
    # 8,192 draws reach most of them, and none beyond
    assert containers // 2 < len(np.unique(rank % containers)) <= containers
    # the numpy source spreads its stream the same way
    py = PySyntheticSource(seed=11, vocab=5 * containers + 3,
                           containers=containers).generate(4096)
    assert int(py.cols["mntns"].max()) - MNTNS_BASE == containers - 1


@pytest.mark.parametrize("value, ok", [("1", True), ("1024", True),
                                       ("0", False), ("1025", False),
                                       ("-3", False)])
def test_containers_is_held_to_upstreams_cap(value, ok):
    for gadget in (("advise", "seccomp-profile"), ("trace", "exec"),
                   ("trace", "tcp")):
        params = get(*gadget).params().to_params()
        assert params.get("containers").as_int() == 64      # the default
        if ok:
            params.set("containers", value)
            assert params.get("containers").as_int() == int(value)
            continue
        with pytest.raises(ParamError) as e:
            params.set("containers", value)
        assert "MaxContainersPerNode" in str(e.value)
        assert str(MAX_CONTAINERS_PER_NODE) in str(e.value)


# -- the served runs ----------------------------------------------------------

def compiles() -> float:
    return snapshot()["ig_jax_backend_compiles_total"]


def slices_total(decision: str) -> float:
    return snapshot().get(f'{SLICES}{{{G},decision="{decision}"}}', 0.0)


def served(seed: int, monkeypatch, **extra) -> dict:
    """One run of the smoke's `anomaly` phase on the dense stream, with
    the compile counter read when the native source starts and when the
    run is over, and the slice counter's movement."""
    marks: dict = {}
    real_start = NativeCapture.start
    real_post = tpusketch.TpuSketchInstance.post_gadget_run

    def start(self):
        marks.setdefault("at_source_start", compiles())
        return real_start(self)

    def post_gadget_run(self):
        # the teardown harvest and the last seal's finish are in here; the
        # reference's replay, which compiles plenty, comes after
        real_post(self)
        marks["at_run_end"] = compiles()

    monkeypatch.setattr(NativeCapture, "start", start)
    monkeypatch.setattr(tpusketch.TpuSketchInstance, "post_gadget_run",
                        post_gadget_run)
    before = {d: slices_total(d) for d in ("admitted", "dropped")}
    run = chip_smoke.anomaly_run(SIZE, seed, extra=extra, dense=True)
    monkeypatch.undo()
    run["compiles_after_source_start"] = (
        marks["at_run_end"] - marks["at_source_start"])
    run["slices_counted"] = {d: slices_total(d) - before[d] for d in before}
    return run


@pytest.fixture(scope="module")
def dense():
    """The dense run at `history-max-slices 4096`, behind a run of the
    same deployment at 64 containers: what a process compiles once for any
    run (the digest, the update ladder, the seal's programs) is compiled
    there, so a compile after the dense run's source has started is one
    its priming missed."""
    ensure_compile_cache()      # the compile counter's listeners
    mp = pytest.MonkeyPatch()
    mp.setitem(chip_smoke.DENSE, "containers", 64)
    mp.setitem(chip_smoke.DENSE, "vocab", 64 * 335)
    chip_smoke.anomaly_run(SIZE, 3400031, dense=True)
    mp.undo()
    try:
        yield served(3400032, mp)
    finally:
        mp.undo()


@pytest.fixture(scope="module")
def capped(dense):
    """The same deployment at the parameter's default, 256 slices."""
    mp = pytest.MonkeyPatch()
    try:
        yield served(3400033, mp, **{"history-max-slices": "256"})
    finally:
        mp.undo()


def start_weights() -> dict:
    return jax.tree.map(np.asarray, ae.ae_init(ae.AEConfig(
        input_dim=256, hidden_dim=256, latent_dim=64)).params)


def test_scores_meet_the_replay_at_1024_containers(dense):
    """Every summary judged, not the smoke's nine; `TOLERANCE`'s reason is
    in chipbench/reference_scorer.py."""
    r = ref.compare(dense["recorded"], start_weights(), 256)
    assert r["score_keys_equal"]
    assert r["scores_compared"] >= CONTAINERS * 20
    assert r["score_gap"] <= ref.TOLERANCE, r


def test_histograms_and_syscall_sets_are_exact_at_1024_containers(dense):
    r = dense["readings"]
    assert r["histograms_exact"] is True and r["profile_exact"] is True
    assert r["harvests"] == dense["pipeline"]["anomaly"]["steps"]
    seen = np.unique(np.concatenate(dense["recorded"].mntns))
    assert len(seen) == CONTAINERS


def test_the_scorer_is_primed_at_the_slots_the_run_reaches(dense):
    assert dense["pipeline"]["anomaly"] == {
        "steps": dense["readings"]["harvests"], "containers": CONTAINERS,
        "slots": CONTAINERS, "primed_slots": CONTAINERS}
    # no program, the scorer's or any other, compiled or read from the
    # cache once the source had started
    assert dense["compiles_after_source_start"] == 0


def batches_of(windows: list, rec) -> list[tuple[np.ndarray, np.ndarray]]:
    """Each window's own batches as (mntns, folded key) columns: a window
    is whole batches, in the order the runtime handed them over."""
    at, out = 0, []
    for win in windows:
        events, mntns, keys = 0, [], []
        while events < win.events:
            mntns.append(rec.mntns[at])
            keys.append(fold64_to_32(rec.keys[at]))
            events += len(rec.mntns[at])
            at += 1
        assert events == win.events
        out.append((mntns, keys))
    return out


def plain_slices(mntns: list[np.ndarray], keys: list[np.ndarray]) -> dict:
    """The plain per-container reference of one window: for each container
    a dict of exact counts by key with the batch each key first came in
    (from which the event count, the distinct keys and the top 32 follow).
    One Python step an event: what the store may never do."""
    out: dict[int, dict] = {}
    for batch, (ns_col, key_col) in enumerate(zip(mntns, keys)):
        for ns, key in zip(ns_col.tolist(), key_col.tolist()):
            c = out.setdefault(ns, {"counts": Counter(), "first": {}})
            c["counts"][key] += 1
            c["first"].setdefault(key, batch)
    return out


def test_every_containers_slice_holds_its_exact_counts(dense):
    windows = dense["windows"]
    assert len(windows) >= 2
    widest = 0
    for win, (mntns, keys) in zip(windows, batches_of(windows,
                                                      dense["recorded"])):
        want = plain_slices(mntns, keys)
        assert win.slices_dropped == 0
        # a slice a container, one a (container, kind) cell, the kind's
        (kind_key,) = [k for k in win.slices if k.startswith("kind:")]
        kind = kind_key.removeprefix("kind:")
        assert sorted(win.slices) == sorted(
            [f"mntns:{ns}" for ns in want]
            + [f"mntns:{ns}|kind:{kind}" for ns in want] + [kind_key])
        for ns, plain in want.items():
            got = win.slices[f"mntns:{ns}"]
            assert got["events"] == sum(plain["counts"].values())
            # count descending, ties by first batch, then by key
            top = sorted(plain["counts"].items(), key=lambda kc: (
                -kc[1], plain["first"][kc[0]], kc[0]))[:SLICE_HH_K]
            assert got["hh"] == top, ns
            distinct = len(plain["counts"])
            m = 1 << SLICE_HLL_P
            # thousands of (container, window) pairs are judged: five
            # standard errors, and at a handful of keys (linear counting)
            # room for a register three of them share
            assert abs(slice_hll_estimate(got["hll"]) - distinct) <= max(
                4.0, 5 * 1.04 / np.sqrt(m) * distinct), ns
            assert int(got["ent"].sum()) == got["events"]
        assert win.slices[kind_key]["events"] == win.events
        # the kind's slice folds every container's cell: tens of thousands
        # of keys, cut by selection before the sort
        whole, first = Counter(), {}
        for plain in want.values():
            whole.update(plain["counts"])
            for key, batch in plain["first"].items():
                first[key] = min(batch, first.get(key, batch))
        widest = max(widest, len(whole))
        assert win.slices[kind_key]["hh"] == sorted(
            whole.items(), key=lambda kc: (-kc[1], first[kc[0]], kc[0])
        )[:SLICE_HH_K]
    assert widest > 1024        # a window on a busy machine may be small
    total = sum(len(w.slices) for w in windows)
    assert dense["slices_counted"] == {"admitted": total, "dropped": 0}
    block = dense["pipeline"]["slices"]
    assert block["dropped"] == 0
    assert block["admitted"] == block["slices"] == 2 * block["cells"] + 1


def test_at_the_default_cap_a_window_drops_exactly_the_slices_over_it(capped):
    """`history-max-slices 256`: a slice is admitted at its first
    appearance or never, so a window that saw `c` containers asked for
    2 c + 1 slices and dropped what is over 256 of them, each once: 1,793
    where it saw all 1,024. The scores and histograms do not care."""
    windows = capped["windows"]
    assert len(windows) >= 2
    dropped = []
    for win, (mntns, _keys) in zip(windows, batches_of(windows,
                                                       capped["recorded"])):
        asked = 2 * len(np.unique(np.concatenate(mntns))) + 1
        assert len(win.slices) == min(asked, 256)
        assert win.slices_dropped == max(asked - 256, 0)
        dropped.append(win.slices_dropped)
    assert 2 * CONTAINERS + 1 - 256 == 1793 and 1793 in dropped
    assert capped["slices_counted"] == {
        "admitted": sum(len(w.slices) for w in windows),
        "dropped": sum(dropped)}
    assert capped["pipeline"]["slices"]["admitted"] <= 256
    assert capped["pipeline"]["slices"]["dropped"] in dropped
    r = capped["readings"]
    assert r["histograms_exact"] and r["profile_exact"]
    assert r["score_gap"] <= ref.TOLERANCE
    assert r["slices_exact"] is False          # the smoke's check sees it
    assert capped["compiles_after_source_start"] == 0


def test_the_dense_run_names_its_stages_and_counters(dense):
    turn = dense["pipeline"]["turn"]
    assert turn["stages"][DISTS_STAGE] > 0.0
    assert 0.0 < turn["anomaly_score_s"] < turn["stages"]["tpusketch_harvest"]
    snap = snapshot()
    for stage in (DISTS_STAGE, "anomaly_score"):
        assert snap[f'ig_pipeline_turn_seconds_total{{stage="{stage}"}}'] > 0
    for decision in ("admitted", "dropped"):
        assert f'{SLICES}{{{G},decision="{decision}"}}' in snap
