"""Event-time windows, served: `trace exec` through `LocalRuntime.run_gadget`
with `history-lateness` on NEXmark's delayed events (the synthetic source's
`prob-delayed-event` / `occasional-delay`), at the rehearsal's geometry and a
tenth of exec-eventtime's time (1 s windows, 300 ms of lateness).

Every event's (key, ts) is recorded, and every sealed window is held to the
plain reference, chipbench/reference_windows.py: its exact count, its
alignment to multiples of W, no event late while no delay passes the
lateness, late events counted (and windows + late == every event) once
delays pass it, the teardown's seals, and two planted faults (a window
sealed before its late events came; every event routed to the newest
window, as the processing clock would) that the comparison must catch.
"""

from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

import inspektor_gadget_tpu.all_gadgets  # noqa: F401
from inspektor_gadget_tpu.gadgets import GadgetContext, get
from inspektor_gadget_tpu.operators.operators import get as get_op
from inspektor_gadget_tpu.operators.tpusketch import TpuSketchInstance
from inspektor_gadget_tpu.params import Collection, ParamError
from inspektor_gadget_tpu.telemetry import snapshot

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [p for p in (str(ROOT),) if p not in sys.path]

import chip_smoke  # noqa: E402

SIZE = chip_smoke.SIZES["cpu"]
G = 'gadget="trace/exec"'
LATE = f"ig_history_late_events_total{{{G}}}"
DELAY = f"ig_history_seal_delay_seconds_{{}}{{{{{G}}}}}"
WIDTH_NS = 1_000_000_000
LATENESS_S = 0.3


def counters() -> dict:
    snap = snapshot()
    return {k: snap.get(k, 0.0) for k in (
        LATE, DELAY.format("sum"), DELAY.format("count"))}


def served(seed: int, fault: str = "", seals: int = 3, behind_s: float = 0.0,
           **source) -> dict:
    before = counters()
    run = chip_smoke.eventtime_run(SIZE, "cpu", seed, fault=fault,
                                   seals=seals, behind_s=behind_s, **source)
    after = counters()
    run["counted"] = {k: after[k] - before[k] for k in after}
    return run


@pytest.fixture(scope="module")
def sound():
    return served(4100001)


@pytest.fixture(scope="module")
def delayed_past_lateness():
    return served(4100002, **{"occasional-delay": "1500ms"})


def test_every_sealed_window_meets_the_plain_reference(sound):
    rows = {k: v["value"] for k, v in sound["compared"].items()}
    assert sound["ok"], sound["compared"]
    assert rows["windows_sealed"] >= 3
    assert rows["events_gap"] == 0 and rows["hh_under"] == 0
    # the stream carried delayed events: about a tenth of them
    ts = sound["ts"].astype(np.int64)
    delayed = (np.maximum.accumulate(ts) - ts) > 2_000_000
    assert 0.05 < delayed.mean() < 0.15


def test_windows_are_aligned_to_multiples_of_the_window(sound):
    for w in sound["windows"]:
        start = round(w.start_ts * 1e9)
        assert start % WIDTH_NS == 0
        assert round(w.end_ts * 1e9) - start == WIDTH_NS
    starts = [w.start_ts for w in sound["windows"]]
    assert starts == sorted(starts) and len(set(starts)) == len(starts)


def test_no_event_is_late_while_no_delay_passes_the_lateness(sound):
    assert sum(w.late for w in sound["windows"]) == 0
    assert sound["counted"][LATE] == 0
    assert sum(w.events for w in sound["windows"]) == sound["events"]
    block = sound["pipeline"]["windows"]
    assert block["late"] == 0 and 1 <= block["open"] <= 2
    # the lateness, and the ring's backlog behind the clock
    assert LATENESS_S <= block["watermark_lag_s"] < LATENESS_S + 3.0


def test_a_seal_comes_the_lateness_after_its_windows_end(sound):
    n = sound["counted"][DELAY.format("count")]
    assert n >= 2
    mean = sound["counted"][DELAY.format("sum")] / n
    assert LATENESS_S <= mean < LATENESS_S + 3.0


def test_past_the_lateness_late_events_are_counted_and_add_up(
        delayed_past_lateness):
    run = delayed_past_lateness
    assert run["ok"], run["compared"]
    late = sum(w.late for w in run["windows"])
    assert late > 0
    assert run["counted"][LATE] == late
    assert sum(w.events for w in run["windows"]) + late == run["events"]
    # what the announcement carried is what the store holds
    assert [h["late"] for h in run["announced"]] == [
        w.late for w in run["windows"]]


def test_a_source_behind_the_clock_by_more_than_a_window_is_not_late():
    """A source that keeps sending while its events run 2.5 s behind the
    operator's clock, more than a whole window (a backlog, an agent
    catching up): the watermark follows the events, not the clock, so no
    event is late and every window meets the reference."""
    run = served(4100003, behind_s=2.5)
    assert run["ok"], run["compared"]
    assert sum(w.late for w in run["windows"]) == 0
    assert run["counted"][LATE] == 0
    assert sum(w.events for w in run["windows"]) == run["events"]
    assert run["pipeline"]["windows"]["watermark_lag_s"] >= 2.5 + LATENESS_S


def test_teardown_seals_every_open_window(sound):
    """The run was cancelled after three seals on the watermark; the
    windows still open (the one the watermark is in and the newest) were
    sealed by the teardown, so the newest event's window is in the store."""
    newest = int(sound["ts"].max())
    last = sound["windows"][-1]
    assert round(last.start_ts * 1e9) <= newest < round(last.end_ts * 1e9)
    assert sound["compared"]["windows_missing"]["value"] == 0
    assert len(sound["windows"]) > 3


@pytest.mark.parametrize("fault, caught_by", [
    ("early", "late"), ("processing", "events_gap")])
def test_a_planted_fault_fails_the_comparison(fault, caught_by):
    run = served(4100010 + len(fault), fault=fault, seals=2)
    assert not run["ok"]
    row = run["compared"][caught_by]
    assert row["value"] > row["limit"]


def operator_params(**extra):
    tp = get_op("tpusketch").instance_params().to_params()
    for k, v in {"enable": "true", "history": "true",
                 "history-interval": "1s", "history-lateness": "300ms",
                 **extra}.items():
        tp.set(k, v)
    return tp


@pytest.mark.parametrize("extra, says", [
    ({"invertible": "true"}, "invertible"),
    ({"quantiles": "true"}, "quantiles"),
    ({"history-interval": "0s"}, "history-interval"),
    ({"history": "false"}, "history true"),
    ({"history-slots": "2"}, "history-slots"),
])
def test_lateness_is_refused_where_it_cannot_hold(extra, says):
    desc = get("trace", "exec")
    ops = Collection()
    ops["operator.tpusketch."] = operator_params(**extra)
    ctx = GadgetContext(desc, gadget_params=desc.params().to_params(),
                        operator_params=ops)
    with pytest.raises(ParamError) as e:
        TpuSketchInstance(get_op("tpusketch"), ctx, None,
                          ops["operator.tpusketch."])
    assert says in str(e.value)


def test_the_loop_hands_its_idle_turns_to_the_operator(tmp_path, monkeypatch):
    """A turn of the source's loop that pops nothing reaches the operator's
    `idle`, where an idle source's watermark follows the clock."""
    from inspektor_gadget_tpu.runtime import LocalRuntime

    idle = TpuSketchInstance.idle
    calls = []

    def counted(self):
        calls.append(self._ev_lo)
        idle(self)

    monkeypatch.setattr(TpuSketchInstance, "idle", counted)
    desc = get("trace", "exec")
    params = desc.params().to_params()
    for k, v in {"source": "synthetic", "rate": "200", "batch-size": "2048",
                 "seed": "4100020"}.items():
        params.set(k, v)
    ops = Collection()
    ops["operator.tpusketch."] = operator_params(**{
        "log2-width": "8", "hll-p": "6", "entropy-log2-width": "6",
        "topk": "64", "history-log2-width": "6", "harvest-interval": "1h",
        "history-dir": str(tmp_path)})
    ctx = GadgetContext(desc, gadget_params=params, operator_params=ops,
                        timeout=1.0)
    result = LocalRuntime().run_gadget(ctx)
    assert not result.errors()
    # turns before the first batch find no window to seal, later ones do
    assert calls and any(lo is not None for lo in calls)


# -- the watermark on hand-made batches ---------------------------------------

BASE_NS = 1_700_000_000 * WIDTH_NS      # a multiple of the window


def test_a_jump_of_the_event_clock_and_an_idle_source(tmp_path):
    """Hand-made batches on an injected clock: a jump of the event clock
    past what the planes hold seals the oldest window early; an event of a
    window the watermark has passed is late, absorbed by the cumulative
    state and carried by the next window sealed; a source that keeps
    sending while it falls behind the clock holds the watermark to its
    events; and once it has been idle for a whole window the watermark
    follows the clock from there, on the loop's idle turns."""
    from inspektor_gadget_tpu.history import HISTORY, decode_window
    from inspektor_gadget_tpu.sources.batch import EventBatch
    from inspektor_gadget_tpu.operators import tpusketch

    clock = [BASE_NS / 1e9]
    ctx = GadgetContext(get("trace", "exec"),
                        extra={"history_clock": lambda: clock[0]})
    ctx.gadget_params.set("batch-size", "8192")
    p = get_op("tpusketch").instance_params().to_params()
    for k, v in {"enable": "true", "log2-width": "8", "hll-p": "6",
                 "entropy-log2-width": "6", "topk": "64",
                 "history-log2-width": "6", "harvest-interval": "1h",
                 "history": "true", "history-interval": "1s",
                 "history-lateness": "300ms",
                 "history-dir": str(tmp_path)}.items():
        p.set(k, v)
    inst = get_op("tpusketch").instantiate(ctx, None, p)
    late0 = snapshot().get(LATE, 0.0)

    def batch(at_s: list[float], now_s: float, n: int = 100) -> int:
        clock[0] = BASE_NS / 1e9 + now_s
        b = EventBatch.alloc(8192, with_comm=False)
        ts = np.repeat(np.array([BASE_NS + round(t * 1e9) for t in at_s],
                                np.uint64), n)
        b.cols["key_hash"][:len(ts)] = np.arange(1, len(ts) + 1)
        b.cols["ts"][:len(ts)] = ts
        b.count = len(ts)
        inst.enrich_batch(b)
        return len(ts)

    try:
        first = batch([0.1, 0.5], 0.5)                  # window 0
        second = batch([5.2], 1.0)     # window 5: 0 seals early, 3-5 open
        assert (inst._ev_lo, inst._ev_hi) == (
            BASE_NS // WIDTH_NS + 4, BASE_NS // WIDTH_NS + 5)
        late = batch([1.5], 1.1)                        # window 1: late
        # batches every half second while the clock runs 4 s ahead of the
        # events: the watermark stays at 5.25 - 0.3, window 4 stays open
        steady = sum(batch([5.25], 1.6 + 0.5 * i) for i in range(16))
        assert inst._ev_lo == BASE_NS // WIDTH_NS + 4
        # idle 2.5 s, a whole window and 1.5 s more: the watermark moves on
        # by 1.5 s (5.25 + 1.5 - 0.3), past windows 4 and 5
        clock[0] = BASE_NS / 1e9 + 9.1 + 2.5
        inst.idle()
        assert inst._ev_lo == BASE_NS // WIDTH_NS + 6
        assert inst._ev_lo > inst._ev_hi                # none open
        inst.post_gadget_run()
    finally:
        with tpusketch._live_mu:
            tpusketch._live.pop(ctx.run_id, None)
        inst._stats.unregister()
        inst._pstats.unregister()
    windows = [decode_window(h, pl) for h, pl in HISTORY.fetch_windows(
        base_dir=str(tmp_path), gadget="trace/exec")]
    HISTORY.close_all()
    # the late events ride the next window sealed, window 4, which holds
    # no event of its own and seals for them
    assert [(round(w.start_ts) - BASE_NS // WIDTH_NS, w.events, w.late)
            for w in windows] == [(0, first, 0), (4, 0, late),
                                  (5, second + steady, 0)]
    assert snapshot().get(LATE, 0.0) - late0 == late
    assert sum(w.events + w.late for w in windows) == (
        first + second + late + steady)
