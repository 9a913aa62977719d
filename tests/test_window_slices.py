"""The open window's slices as one grouped array store (ISSUE 28).

`WindowSlices` absorbs a batch in one grouped pass and folds the
per-container and per-kind slices out of (mntns, kind) cells at the seal.
What it seals must be what the loop it replaced sealed, byte for byte: the
loop is kept here as the oracle (`np.unique`, a mask per subpopulation,
`SliceSketch.update`, the `dict` sort at the seal), and every case compares
all of a window's slices and the window's digest with it.
"""

from __future__ import annotations

import tempfile

import numpy as np
import pytest

import inspektor_gadget_tpu.all_gadgets  # noqa: F401
from inspektor_gadget_tpu.gadgets import GadgetContext, get
from inspektor_gadget_tpu.history import (
    HISTORY,
    SealedWindow,
    SliceSketch,
    WindowSlices,
    decode_window,
    window_digest,
)
from inspektor_gadget_tpu.history import window as window_mod
from inspektor_gadget_tpu.history.window import (
    SLICE_HH_K,
    SLICE_HLL_P,
    _cell_codes,
    _slice_hll_lanes,
)
from inspektor_gadget_tpu.operators.operators import get as get_op
from inspektor_gadget_tpu.ops import fold64_to_32
from inspektor_gadget_tpu.ops.hashing import fmix32_np
from inspektor_gadget_tpu.params import Collection
from inspektor_gadget_tpu.runtime.local import LocalRuntime
from inspektor_gadget_tpu.telemetry import snapshot

FIRST_NS = 4026531840      # where the kernel's mount namespace ids start


class LoopSlices:
    """The parent's `_accumulate_slices` and seal, as they were."""

    def __init__(self, max_slices: int) -> None:
        self.max_slices = max_slices
        self.slices: dict[str, SliceSketch] = {}
        self.dropped_keys: set[str] = set()

    def absorb(self, mntns, kind, hh, distinct, dist) -> None:
        def feed(key: str, sel: np.ndarray) -> None:
            s = self.slices.get(key)
            if s is None:
                if len(self.slices) >= self.max_slices:
                    self.dropped_keys.add(key)
                    return
                s = self.slices[key] = SliceSketch()
            s.update(hh[sel], distinct[sel], dist[sel])

        for ns in np.unique(mntns):
            sel = mntns == ns
            feed(f"mntns:{int(ns)}", sel)
            for k in np.unique(kind[sel]):
                feed(f"mntns:{int(ns)}|kind:{int(k)}", sel & (kind == k))
        for k in np.unique(kind):
            feed(f"kind:{int(k)}", kind == k)

    def seal(self) -> dict[str, dict]:
        return {key: {"events": s.events, "hll": s.hll, "ent": s.ent,
                      "hh": s.sealed_hh()}
                for key, s in self.slices.items()}

    @property
    def dropped(self) -> int:
        return len(self.dropped_keys)

    @property
    def hh_entries(self) -> int:
        return sum(len(s.hh) for key, s in self.slices.items() if "|" in key)


def window_of(slices: dict[str, dict], dropped: int) -> SealedWindow:
    return SealedWindow(
        gadget="trace/exec", node="n", run_id="r", window=1, start_ts=0.0,
        end_ts=1.0, events=0, drops=0, cms=np.zeros((2, 4), np.int32),
        hll=np.zeros(4, np.int32), ent=np.zeros(4, np.float32),
        topk_keys=np.zeros(0, np.uint32), topk_counts=np.zeros(0, np.int64),
        slices=slices, slices_dropped=dropped)


def assert_same_window(got: dict[str, dict], want: dict[str, dict],
                       got_dropped: int, want_dropped: int) -> None:
    assert list(got) == list(want)          # the same keys, admission order
    for key, w in want.items():
        g = got[key]
        assert g["events"] == w["events"], key
        assert isinstance(g["events"], int)
        for lane in ("hll", "ent"):
            assert g[lane].dtype == w[lane].dtype, (key, lane)
            assert g[lane].shape == w[lane].shape, (key, lane)
            assert np.array_equal(g[lane], w[lane]), (key, lane)
        assert g["hh"] == w["hh"], key
        assert all(type(k) is int and type(c) is int for k, c in g["hh"])
    assert got_dropped == want_dropped
    assert (window_digest(window_of(got, got_dropped))
            == window_digest(window_of(want, want_dropped)))


def both(batches, max_slices: int = 4096):
    """Feed the store and the loop the same batches; compare; return both."""
    store, loop = WindowSlices(max_slices), LoopSlices(max_slices)
    for b in batches:
        store.absorb(*b)
        loop.absorb(*b)
    sealed = store.seal()
    assert_same_window(sealed, loop.seal(), store.dropped, loop.dropped)
    assert len(store) == len(loop.slices)
    return store, loop, sealed


def draw(rng, n: int, *, containers, kinds: int, keys: int, skew: float,
         zero_share: float = 0.0):
    """One batch: `n` events over the given container ids."""
    p = 1.0 / np.arange(1, keys + 1) ** skew
    hh = (rng.choice(keys, size=n, p=p / p.sum()) + 1).astype(np.uint32)
    hh[rng.random(n) < zero_share] = 0
    mntns = np.asarray(containers, np.uint64)[rng.integers(
        0, len(containers), n)]
    kind = rng.integers(1, kinds + 1, n).astype(np.uint32)
    distinct = rng.integers(0, 2**32, n, dtype=np.uint64).astype(np.uint32)
    return mntns, kind, hh, distinct, hh ^ np.uint32(0x5bd1e995)


def narrow(n: int) -> np.ndarray:
    return np.arange(FIRST_NS, FIRST_NS + n, dtype=np.uint64)


def stream(name: str) -> list:
    rng = np.random.default_rng(sum(map(ord, name)))
    sizes = (3000, 41, 5000, 1, 2200, 4096, 700)
    if name == "skewed":
        # heavy skew, unequal batches, 3 kinds x 72 containers
        return [draw(rng, n, containers=narrow(72), kinds=3, keys=400,
                     skew=1.3, zero_share=0.02) for n in sizes]
    if name == "wide-ids":
        # ids all over 64 bits: the lookup table gives way to the sort
        ids = rng.integers(0, 2**63, 75).astype(np.uint64) * np.uint64(2)
        return [draw(rng, n, containers=ids, kinds=4, keys=300, skew=1.1,
                     zero_share=0.01) for n in sizes]
    if name == "zero-keys":
        return [draw(rng, n, containers=narrow(70), kinds=3, keys=50,
                     skew=0.5, zero_share=0.6) for n in sizes]
    if name == "many-kinds":
        return [draw(rng, n, containers=narrow(5), kinds=40, keys=2000,
                     skew=0.8) for n in sizes]
    assert name == "ties"
    # every key of a batch equally often, new keys arriving in later
    # batches: far more than SLICE_HH_K equal counts at the cut, so the
    # table is decided by first appearance alone
    out = []
    for i, reps in enumerate((2, 1, 2, 1, 1)):
        keys = np.arange(1 + 30 * i, 120 + 30 * i, dtype=np.uint32)[::-1]
        hh = np.repeat(keys, reps * 6)
        cell = np.arange(len(hh))
        mntns = narrow(3)[cell % 3]
        kind = (1 + (cell // 3) % 2).astype(np.uint32)
        out.append((mntns, kind, hh, hh, hh))
    return out


@pytest.mark.parametrize(
    "name", ["skewed", "wide-ids", "zero-keys", "many-kinds", "ties"])
def test_store_seals_what_the_loop_sealed(name):
    store, loop, sealed = both(stream(name))
    assert store.dropped == 0
    assert store.hh_entries == loop.hh_entries
    assert store.cells == sum("|" in key for key in sealed)
    if name == "ties":
        hh = sealed[f"mntns:{FIRST_NS}"]["hh"]
        assert len(hh) == SLICE_HH_K
        assert len({c for _, c in hh}) < 4       # ties all the way down
    if name == "zero-keys":
        assert all(k for s in sealed.values() for k, _ in s["hh"])


@pytest.mark.parametrize("name", ["skewed", "ties"])
def test_early_folds_of_the_backlog_change_nothing(name, monkeypatch):
    """The backlog folded after every batch, and with two bits to number
    its batches, seals what one fold at the seal does."""
    monkeypatch.setattr(window_mod, "_MIN_BACKLOG_EVENTS", 0)
    monkeypatch.setattr(window_mod, "_BACKLOG_PER_ENTRY", 0)
    both(stream(name))
    monkeypatch.setattr(window_mod, "_MIN_BACKLOG_EVENTS", 1 << 30)
    monkeypatch.setattr(WindowSlices, "_ord_bits", 2)
    store, _, _ = both(stream(name) * 3)
    assert store._batches > 1 << 2


@pytest.mark.parametrize("name", ["skewed", "ties"])
def test_a_fold_put_off_changes_nothing(name, monkeypatch):
    """`absorb(..., fold=False)` (a summary is nearly due, ISSUE 33)
    leaves a backlog over its threshold unfolded; the next call that may
    fold folds it, and the window is what it would have been."""
    monkeypatch.setattr(window_mod, "_MIN_BACKLOG_EVENTS", 0)
    monkeypatch.setattr(window_mod, "_BACKLOG_PER_ENTRY", 0)
    batches = stream(name)
    reference, _loop, want = both(batches)
    store = WindowSlices(4096)
    for i, lanes in enumerate(batches):
        store.absorb(*lanes, fold=i % 3 == 2)
        assert len(store._backlog) == (0 if i % 3 == 2 else i % 3 + 1)
    assert_same_window(store.seal(), want, store.dropped, reference.dropped)


@pytest.mark.parametrize("cap", [0, 4, 8, 130])
def test_the_cap_admits_what_the_loop_admitted(cap):
    store, loop, sealed = both(stream("skewed"), cap)
    assert len(sealed) == cap
    assert store.dropped == len(loop.dropped_keys) > 0
    if cap == 130:
        # 32 containers whole, then one whose own slice got in and whose
        # crosses (all but one) did not
        ns = [k for k in sealed if "|" not in k and k.startswith("mntns:")]
        split = [k for k in ns
                 if sum(c.startswith(k + "|") for c in sealed) < 3]
        assert len(split) == 1
        assert sealed[split[0]]["events"] > sum(
            s["events"] for k, s in sealed.items()
            if k.startswith(split[0] + "|"))


def test_the_cap_reached_after_the_kinds_were_admitted():
    """Two containers first, so the kinds get in; the containers of later
    batches do not, and their cells keep state for the kinds alone, in
    one row a kind."""
    rng = np.random.default_rng(28)
    batches = [draw(rng, 500, containers=narrow(2), kinds=3, keys=90,
                    skew=1.0)]
    batches += [draw(rng, n, containers=narrow(72), kinds=3, keys=90,
                     skew=1.0, zero_share=0.05) for n in (2000, 900, 3100)]
    for cap in (9, 11, 14):
        store, loop, sealed = both(batches, cap)
        assert "kind:1" in sealed
        held = {k.split("|")[0] for k in sealed if k.startswith("mntns:")}
        assert store.cells <= 3 * (len(held) + 1) < len(store._cell_words)
        assert sealed["kind:1"]["events"] > sum(
            s["events"] for k, s in sealed.items() if k.endswith("|kind:1"))


@pytest.mark.parametrize("shape", ["empty", "one-event", "one-cell"])
def test_small_batches(shape):
    rng = np.random.default_rng(3)
    full = draw(rng, 800, containers=narrow(4), kinds=2, keys=30, skew=1.0)
    if shape == "empty":
        none = tuple(lane[:0] for lane in full)
        store = WindowSlices(16)
        store.absorb(*none)
        assert store.seal() == {} and len(store) == 0 and store.cells == 0
        store, loop = WindowSlices(16), LoopSlices(16)
        for b in (none, full, none):
            store.absorb(*b)
        loop.absorb(*full)
        assert_same_window(store.seal(), loop.seal(), 0, 0)
    elif shape == "one-event":
        one = tuple(lane[:1] for lane in full)
        _, _, sealed = both([one])
        assert [s["events"] for s in sealed.values()] == [1, 1, 1]
        both([one, full, tuple(lane[5:6] for lane in full)])
    else:
        cell = (full[0] == full[0][0]) & (full[1] == full[1][0])
        _, _, sealed = both([tuple(lane[cell] for lane in full)] * 2)
        assert len(sealed) == 3
        assert len({s["events"] for s in sealed.values()}) == 1


@pytest.mark.parametrize("cap", [4096, 5])
def test_one_lane_for_both_streams(cap):
    """Without `dist` the distribution stream is the distinct lane (what
    the operator hands in where both name one column), kept cells only."""
    rng = np.random.default_rng(13)
    store, loop = WindowSlices(cap), LoopSlices(cap)
    for n in (1500, 40, 2200):
        mntns, kind, hh, distinct, _dist = draw(
            rng, n, containers=narrow(6), kinds=2, keys=50, skew=1.1)
        store.absorb(mntns, kind, hh, distinct)
        loop.absorb(mntns, kind, hh, distinct, distinct)
    assert_same_window(store.seal(), loop.seal(), store.dropped, loop.dropped)
    assert (store.dropped > 0) == (cap == 5)


@pytest.mark.parametrize("case", ["narrow", "wide-ids", "wide-kinds",
                                  "one-cell", "top-of-u64", "one-event"])
def test_cell_codes_is_unique_over_the_pair(case):
    rng = np.random.default_rng(5)
    n = 5000
    few_kinds = rng.integers(1, 4, n).astype(np.uint32)
    mntns, kind = {
        "narrow": (FIRST_NS + rng.integers(0, 64, n).astype(np.uint64),
                   few_kinds),
        "wide-ids": (rng.integers(0, 2**63, 40).astype(np.uint64)[
            rng.integers(0, 40, n)] * np.uint64(2) + np.uint64(1), few_kinds),
        "wide-kinds": (FIRST_NS + rng.integers(0, 64, n).astype(np.uint64),
                       rng.integers(0, 9, n).astype(np.uint32)
                       * np.uint32(500_000_000)),
        "one-cell": (np.full(300, FIRST_NS, np.uint64),
                     np.full(300, 59, np.uint32)),
        "top-of-u64": (np.uint64(2**64 - 1)
                       - rng.integers(0, 3, n).astype(np.uint64), few_kinds),
        "one-event": (np.array([0], np.uint64), np.array([0], np.uint32)),
    }[case]
    cell_ns, cell_kind, code = _cell_codes(mntns, kind)
    want, want_code = np.unique(
        np.stack([mntns, kind.astype(np.uint64)], axis=1), axis=0,
        return_inverse=True)
    assert cell_ns.dtype == mntns.dtype and cell_kind.dtype == kind.dtype
    assert np.array_equal(cell_ns, want[:, 0])
    assert np.array_equal(cell_kind, want[:, 1])
    assert np.array_equal(code, want_code.reshape(-1))


def test_slice_hll_lanes_are_index_and_leading_zeros():
    keys = np.concatenate([
        np.arange(4096, dtype=np.uint32),
        np.random.default_rng(9).integers(0, 2**32, 4096, dtype=np.uint64)
        .astype(np.uint32)])
    idx, rank = _slice_hll_lanes(fmix32_np(keys))
    assert idx.dtype == np.int64 and rank.dtype == np.uint8
    for h, i, r in zip(fmix32_np(keys).tolist(), idx.tolist(), rank.tolist()):
        rest = h & ((1 << (32 - SLICE_HLL_P)) - 1)
        assert i == h >> (32 - SLICE_HLL_P)
        assert r == (32 - SLICE_HLL_P) - rest.bit_length() + 1


# -- the served path ---------------------------------------------------------

HH_ENTRIES = 'ig_history_slice_hh_entries_total{gadget="trace/exec"}'


def test_served_windows_hold_what_the_loop_would_have_sealed():
    """`LocalRuntime.run_gadget` with history on: every sealed window's
    slices equal the loop's, fed the batches the runtime handed to
    `on_batch`; nothing is carried over a seal; the counter and
    `summary.pipeline["slices"]` read what the windows held."""
    desc = get("trace", "exec")
    params = desc.params().to_params()
    for k, v in (("source", "synthetic"), ("rate", "200000"),
                 ("batch-size", "1024"), ("vocab", "500")):
        params.set(k, v)
    sp = get_op("tpusketch").instance_params().to_params()
    tapped: list[tuple] = []
    sealed_at_summary: list[tuple[int, dict | None]] = []
    headers: list[dict] = []

    def on_batch(batch) -> None:
        n = batch.count
        key = fold64_to_32(batch.cols["key_hash"][:n])
        tapped.append((batch.cols["mntns"][:n].copy(),
                       batch.cols["kind"][:n].copy(), key))
        if len(tapped) >= 160:
            ctx.cancel()

    def on_summary(summary) -> None:
        sealed_at_summary.append((len(headers),
                                  summary.pipeline.get("slices")))

    entries0 = snapshot().get(HH_ENTRIES, 0.0)
    with tempfile.TemporaryDirectory(prefix="slices-hist-") as d:
        for k, v in (("enable", "true"), ("depth", "2"), ("log2-width", "8"),
                     ("hll-p", "6"), ("entropy-log2-width", "6"),
                     ("topk", "8"), ("harvest-interval", "50ms"),
                     ("history", "true"), ("history-interval", "150ms"),
                     ("history-log2-width", "6"), ("history-dir", d)):
            sp.set(k, v)
        ops = Collection()
        ops["operator.tpusketch."] = sp
        ctx = GadgetContext(desc, gadget_params=params, operator_params=ops,
                            timeout=60.0,
                            extra={"on_sketch_summary": on_summary,
                                   "on_window_sealed": headers.append})
        try:
            result = LocalRuntime().run_gadget(ctx, on_batch=on_batch)
        finally:
            HISTORY.close_all()
        assert not result.errors(), result.errors()
        windows = [decode_window(h, p) for h, p in
                   HISTORY.fetch_windows(base_dir=d, gadget="trace/exec")]
    assert len(windows) == len(headers) >= 3
    assert [w.digest for w in windows] == [h["digest"] for h in headers]

    at = 0
    stats = []
    for win in windows:
        loop = LoopSlices(256)
        events = 0
        while events < win.events:
            mntns, kind, key = tapped[at]
            loop.absorb(mntns, kind, key, key, key)
            events += len(key)
            at += 1
        assert events == win.events     # a window is whole batches
        want = loop.seal()
        assert sorted(win.slices) == sorted(want)
        for key, w in want.items():
            g = win.slices[key]
            assert g["events"] == w["events"]
            assert np.array_equal(g["hll"], w["hll"])
            assert np.array_equal(g["ent"], w["ent"])
            assert g["hh"] == w["hh"], key
        assert win.slices_dropped == 0
        stats.append({"slices": len(want), "cells": sum(
            "|" in k for k in want), "hh_entries": loop.hh_entries,
            "admitted": len(want), "dropped": 0})
    assert at == len(tapped)
    # one kind: a slice a container, one a cell, and the kind's
    assert all(s["slices"] == 2 * s["cells"] + 1 for s in stats)
    assert max(s["cells"] for s in stats) == 64
    assert (snapshot().get(HH_ENTRIES, 0.0) - entries0
            == sum(s["hh_entries"] for s in stats))
    # a summary carries the block of the last window sealed before it
    for n_sealed, block in sealed_at_summary:
        assert block == (stats[n_sealed - 1] if n_sealed else None)
    assert sealed_at_summary[-1][0] >= 2
