"""The two halves of a seal (ISSUE 33): the served path captures a window
on the loop thread at its batch boundary and finishes it on one worker
thread; `seal_window()` stays synchronous for every caller that reads the
store after it.

What is held here, on one chip and under `shard-ingest` on the CPU's four
devices: the windows a served run's worker puts in the store are, bit for
bit and in order, what a twin sealing synchronously at the same batch
boundaries puts there (a); teardown with a window pending loses none (b);
a worker held back makes the loop wait at the next boundary, never holds
more than one window, and the wait is counted (c); a failing append is
counted once, announces nothing and folds no standing query (d); the
`pipeline["seal"]` block and the three metrics carry what happened (e).
"""

from __future__ import annotations

import copy
import json
import tempfile
import threading
import time

import pytest

import inspektor_gadget_tpu.all_gadgets  # noqa: F401
from inspektor_gadget_tpu.gadgets import GadgetContext, get
from inspektor_gadget_tpu.gadgets.source_gadget import SourceTraceGadget
from inspektor_gadget_tpu.history import (HISTORY, decode_window,
                                          window_digest)
from inspektor_gadget_tpu.operators import tpusketch
from inspektor_gadget_tpu.operators.operators import get as get_op
from inspektor_gadget_tpu.params import Collection
from inspektor_gadget_tpu.runtime.local import LocalRuntime
from inspektor_gadget_tpu.telemetry import snapshot

GADGET = "trace/exec"
LAYOUTS = {"one-chip": {}, "shard-ingest": {"shard-ingest": "true",
                                            "chips": "4"}}
SEALS = 'ig_tpusketch_seals_total{{gadget="trace/exec",finish="{}"}}'
WAITS = 'ig_tpusketch_seal_waits_total{gadget="trace/exec"}'
FINISH = 'ig_tpusketch_seal_finish_seconds_{}{{gadget="trace/exec"}}'
SEAL_DROPS = 'ig_history_drops_total{reason="seal"}'
QDOC = json.dumps([{"id": "hot", "stats": ["topk", "cardinality"],
                    "range": "1h", "top": 8}])


def operator_params(history_dir: str, **more: str):
    p = get_op("tpusketch").instance_params().to_params()
    for k, v in {"enable": "true", "depth": "2", "log2-width": "8",
                 "hll-p": "6", "entropy-log2-width": "6", "topk": "16",
                 "history": "true", "history-log2-width": "6",
                 "history-dir": history_dir, **more}.items():
        p.set(k, v)
    return p


def stored(history_dir: str) -> list:
    """The windows of a store, decoded, in the order they were appended."""
    return [decode_window(h, payload) for h, payload in
            HISTORY.fetch_windows(base_dir=history_dir, gadget=GADGET)]


class Vocabulary:
    """Stands in for the served run's gadget in its twin: the answers the
    native vocabulary gave, by key hash."""

    def __init__(self, known: dict[int, str]):
        self.known = known

    def resolve_key(self, key_hash: int) -> str:
        return self.known.get(int(key_hash), "")

    def resolve_keys_bulk(self, keys) -> list[str]:
        return [self.resolve_key(k) for k in keys]


def delta(after: dict, before: dict, key: str) -> float:
    return after.get(key, 0.0) - before.get(key, 0.0)


def serve(history_dir: str, layout: dict, *, windows: int, patch=None,
          **more: str) -> dict:
    """One served `trace exec` run (native synthetic source, history on at
    120 ms windows), cancelled two batches after its `windows`-th
    interval-driven capture, so the teardown has a window of its own to
    seal. `patch(win)` runs in front of every append. Keeps what a twin
    needs (a copy of every batch, the batches its summaries and its
    captures closed) and what the worker did."""
    desc = get("trace", "exec")
    params = desc.params().to_params()
    for k, v in (("source", "synthetic"), ("rate", "200000"),
                 ("seed", "3300033"), ("batch-size", "1024"),
                 ("vocab", "400")):
        params.set(k, v)
    run = {"batches": [], "harvest_at": [], "seal_at": [], "announced": [],
           "summaries": [], "answers": [], "held": [], "appending": [0, 0],
           "vocabulary": {}}
    capture = tpusketch.TpuSketchInstance._capture_window
    append = HISTORY.append_window
    resolve_key = SourceTraceGadget.resolve_key
    resolve_keys_bulk = SourceTraceGadget.resolve_keys_bulk

    def noted_key(gadget, key_hash):
        name = resolve_key(gadget, key_hash)
        if name:        # after the run's sources are closed: none
            run["vocabulary"][int(key_hash)] = name
        return name

    def noted_bulk(gadget, keys):
        names = resolve_keys_bulk(gadget, keys)
        run["vocabulary"].update(
            (int(k), name) for k, name in zip(keys, names) if name)
        return names

    def marked_capture(inst):
        # the drain stands before the capture: nothing is with the worker
        run["held"].append(inst._seal_thread)
        cap = capture(inst)
        if cap is not None and not ctx.done:    # the teardown's is no mark
            run["seal_at"].append(len(run["batches"]))
        return cap

    def counted_append(win, *, writer):
        now, most = run["appending"]
        run["appending"][:] = [now + 1, max(most, now + 1)]
        try:
            if patch is not None:
                patch(win)
            return append(win, writer=writer)
        finally:
            run["appending"][0] -= 1

    def on_summary(summary) -> None:
        run["summaries"].append(summary)
        run["harvest_at"].append(len(run["batches"]))

    def on_batch(batch) -> None:
        run["batches"].append(copy.deepcopy(batch))
        marks = run["seal_at"]
        if len(marks) >= windows and len(run["batches"]) > marks[windows - 1] + 2:
            ctx.cancel()

    ops = Collection()
    ops["operator.tpusketch."] = operator_params(
        history_dir, **{"harvest-interval": "50ms",
                        "history-interval": "120ms", **layout, **more})
    ctx = GadgetContext(
        desc, gadget_params=params, operator_params=ops, timeout=120.0,
        extra={"on_sketch_summary": on_summary,
               "on_window_sealed": lambda h: run["announced"].append(
                   (h, threading.current_thread().name)),
               "on_query_answer": lambda h, _p: run["answers"].append(h)})
    before = snapshot()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(tpusketch.TpuSketchInstance, "_capture_window",
                       marked_capture)
            mp.setattr(HISTORY, "append_window", counted_append)
            mp.setattr(SourceTraceGadget, "resolve_key", noted_key)
            mp.setattr(SourceTraceGadget, "resolve_keys_bulk", noted_bulk)
            result = LocalRuntime().run_gadget(ctx, on_batch=on_batch)
    finally:
        HISTORY.close_all()
    assert not result.errors(), result.errors()
    run["metrics"] = {k: delta(snapshot(), before, k) for k in (
        SEALS.format("worker"), SEALS.format("caller"), WAITS,
        FINISH.format("count"), FINISH.format("sum"), SEAL_DROPS)}
    run["stored"] = stored(history_dir)
    return run


WINDOWS = 4     # interval-driven seals of the run the first tests share


@pytest.fixture(scope="module", params=list(LAYOUTS))
def served(request):
    def hold_the_last(win) -> None:
        # the window the teardown finds with the worker stays there while
        # the teardown's summary is made
        if win.window >= WINDOWS:
            time.sleep(0.3)

    with tempfile.TemporaryDirectory(prefix="seal-worker-") as d:
        yield {"layout": LAYOUTS[request.param], **serve(
            d, LAYOUTS[request.param], windows=WINDOWS, patch=hold_the_last)}


def test_the_worker_seals_what_a_synchronous_twin_seals(served):
    """(a) The served run's batches through a hand-made instance of the
    same layout, harvested and sealed with `seal_window()` at the batch
    boundaries the served run harvested and captured at."""
    batches = served["batches"]
    harvest_at, seal_at = set(served["harvest_at"]), set(served["seal_at"])
    desc = get("trace", "exec")
    ctx = GadgetContext(desc)
    ctx.gadget_params.set("batch-size", "1024")
    with tempfile.TemporaryDirectory(prefix="seal-twin-") as d:
        inst = get_op("tpusketch").instantiate(
            ctx, Vocabulary(served["vocabulary"]), operator_params(
            d, **{"harvest-interval": "1h", "history-interval": "1h",
                  **served["layout"]}))
        try:
            for i, batch in enumerate(batches):
                inst.enrich_batch(batch)
                # a mark is the count of batches tapped before the hook
                # fired, inside the turn of batch `i`, ahead of its tap
                if i in harvest_at:
                    inst.harvest()
                if i in seal_at:
                    inst.seal_window()
            # the served run's teardown comes after its sources are closed
            inst.gadget.known = {}
            inst.post_gadget_run()
        finally:
            HISTORY.close_all()
        twin = stored(d)
    got = served["stored"]
    assert len(got) == len(twin) > WINDOWS
    assert [w.window for w in got] == list(range(1, len(got) + 1))
    for mine, theirs in zip(got, twin):
        assert (mine.window, mine.events, mine.drops, mine.digest) == (
            theirs.window, theirs.events, theirs.drops, theirs.digest)
        assert mine.names == theirs.names
        assert list(mine.slices) == list(theirs.slices)
        # what the store holds is what was digested
        assert window_digest(mine) == mine.digest
    assert all(w.names and w.slices for w in got)


def test_teardown_with_a_window_pending_loses_nothing(served):
    """(b) The run was cancelled while the worker held a window; the
    teardown's summary saw it there, and the teardown's own seal waited
    for it."""
    got = served["stored"]
    assert served["summaries"][-1].pipeline["seal"]["pending"] == 1
    absorbed = sum(b.count for b in served["batches"])
    assert sum(w.events for w in got) == absorbed
    assert [h["window"] for h, _t in served["announced"]] == [
        w.window for w in got]
    # every window but the teardown's came from the worker's thread
    threads = [t for _h, t in served["announced"]]
    assert all(t.startswith("tpusketch-seal-") for t in threads[:-1])
    assert threads[-1] == threading.current_thread().name


def test_the_block_and_the_metrics_carry_what_happened(served):
    """(e) The worker finished every interval-driven seal and the caller
    the teardown's; the block says what the metrics say."""
    got, m = served["stored"], served["metrics"]
    marks = len(served["seal_at"])
    assert m[SEALS.format("worker")] == marks == len(got) - 1
    assert m[SEALS.format("caller")] == 1
    assert m[FINISH.format("count")] == len(got) and m[FINISH.format("sum")] > 0
    seal = served["summaries"][-1].pipeline["seal"]
    assert set(seal) == {"worker", "caller", "waited", "pending",
                         "finish_ms_last", "finish_ms_max"}
    # the teardown's summary goes before the teardown's seal, and the
    # window the worker holds is counted when it is finished
    assert (seal["worker"], seal["caller"], seal["pending"]) == (
        marks - 1, 0, 1)
    assert seal["waited"] == m[WAITS]
    assert 0 < seal["finish_ms_last"] <= seal["finish_ms_max"]
    assert all(held is None for held in served["held"])
    assert served["appending"] == [0, 1]


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_a_worker_held_back_makes_the_loop_wait(layout):
    """(c) Every append takes longer than a window: each boundary after
    the first finds the worker on the window before and waits for it."""
    def slow(_win) -> None:
        time.sleep(0.2)

    with tempfile.TemporaryDirectory(prefix="seal-slow-") as d:
        run = serve(d, LAYOUTS[layout], windows=3, patch=slow)
    got, m = run["stored"], run["metrics"]
    marks = len(run["seal_at"])
    assert [w.window for w in got] == list(range(1, marks + 2))
    assert sum(w.events for w in got) == sum(b.count for b in run["batches"])
    assert m[WAITS] == marks - 1 >= 2
    assert run["summaries"][-1].pipeline["seal"]["waited"] == m[WAITS]
    assert m[SEALS.format("worker")] == marks
    # never two windows with the worker, never two appends at once
    assert all(held is None for held in run["held"])
    assert run["appending"] == [0, 1]
    # the wait is the loop's stall: the stage holds it
    stages = run["summaries"][-1].pipeline["turn"]["stages"]
    assert stages["tpusketch_seal"] >= 0.1


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_a_failing_append_is_counted_once_and_announces_nothing(layout):
    """(d) The second window's append fails on the worker's thread."""
    def fail_second(win) -> None:
        if win.window == 2:
            raise ValueError("planted: the store refuses window 2")

    with tempfile.TemporaryDirectory(prefix="seal-fail-") as d:
        run = serve(d, LAYOUTS[layout], windows=4, patch=fail_second,
                    **{"standing-queries": QDOC})
    got, m = run["stored"], run["metrics"]
    assert m[SEAL_DROPS] == 1
    kept = [w.window for w in got]
    assert 2 not in kept and kept == sorted(kept) and len(kept) >= 3
    assert [h["window"] for h, _t in run["announced"]] == kept
    # a standing query folds the windows the store took, one answer each
    assert [h["windows"] for h in run["answers"]] == list(
        range(1, len(kept) + 1))
    # the failed window was still a seal the worker finished
    assert m[SEALS.format("worker")] + m[SEALS.format("caller")] == len(kept) + 1


def test_the_worker_stands_still_while_a_summary_is_nearly_due(tmp_path):
    """The loop thread closes the worker's gate `SEAL_QUIET_S` before a
    summary is due and opens it in the turn behind the summary; a finish on
    the worker starts no step behind a closed gate; a caller's drain opens
    it."""
    announced: list[int] = []
    desc = get("trace", "exec")
    ctx = GadgetContext(desc, extra={
        "on_window_sealed": lambda h: announced.append(h["window"])})
    ctx.gadget_params.set("batch-size", "1024")
    inst = get_op("tpusketch").instantiate(ctx, None, operator_params(
        str(tmp_path), **{"harvest-interval": "1s", "history-interval": "1h"}))
    assert inst._seal_quiet == tpusketch.SEAL_QUIET_S
    from inspektor_gadget_tpu.sources.synthetic import PySyntheticSource
    src = PySyntheticSource(seed=33, vocab=50, batch_size=256)
    inst.pre_gadget_run()       # no compile inside the turns timed below
    try:
        for since, want in ((0.5, True), (0.97, False), (0.5, True),
                            (1.5, False)):
            inst._last_harvest = time.monotonic() - since
            inst.enrich_batch(src.pop())
            assert inst._seal_clear.is_set() is want, since
        assert inst._epoch == 1         # the last of them harvested
        inst.enrich_batch(src.pop())    # the turn behind the summary
        assert inst._seal_clear.is_set()
        cap = inst._capture_window()
        inst._seal_clear.clear()
        inst._seal_thread = worker = threading.Thread(
            target=inst._finish_on_worker, args=(cap,), daemon=True)
        worker.start()
        worker.join(0.2)
        assert worker.is_alive() and not announced
        inst.enrich_batch(src.pop())    # a turn far from a summary opens it
        worker.join(10.0)
        assert not worker.is_alive() and announced == [1]
        # a synchronous seal does not wait behind a closed gate either
        cap = inst._capture_window()
        inst._seal_clear.clear()
        inst._seal_thread = worker = threading.Thread(
            target=inst._finish_on_worker, args=(cap,), daemon=True)
        worker.start()
        inst.enrich_batch(src.pop())
        inst._seal_clear.clear()
        inst.seal_window()
        assert not worker.is_alive() and announced == [1, 2, 3]
    finally:
        inst.post_gadget_run()
        HISTORY.close_all()
