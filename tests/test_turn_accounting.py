"""Turn accounting (ISSUE 25): the batch turn timed stage by stage on the
loop thread, from `src.pop()` to the seal.

What is held here: the stages tile the turn (a), their `ig:` annotations
are siblings that cover the loop thread's time (b), the slowest turns are
kept with their stage split and the thread's CPU time (c), the helper
costs next to nothing (d), nothing per stage reaches the tracer's ring
(e), and backend compiles are counted (f).
"""

from __future__ import annotations

import tempfile
import threading
import time

import pytest

import inspektor_gadget_tpu.all_gadgets  # noqa: F401
from inspektor_gadget_tpu.gadgets import GadgetContext, get
from inspektor_gadget_tpu.operators import tpusketch
from inspektor_gadget_tpu.operators.operators import get as get_op
from inspektor_gadget_tpu.params import Collection
from inspektor_gadget_tpu.runtime.local import LocalRuntime
from inspektor_gadget_tpu.telemetry import snapshot, tracing
from inspektor_gadget_tpu.telemetry.pipeline import (
    ANOMALY_SCORE,
    HARVEST_WAIT,
    OPTIONAL_STAGES,
    SLOW_TURNS,
    TURN_STAGES,
    PipelineStats,
    TurnClock,
)
from inspektor_gadget_tpu.telemetry.tracing import TRACER

STEPS = 'ig_tpusketch_steps_total{gadget="trace/exec"}'
# what a one-chip run of a gadget that records nothing, with the anomaly
# scorer off, carries (the stages it had before ISSUE 31 named the optional
# ones), and of it what a run with history on and no priority classes must
# have timed
ONE_CHIP = set(TURN_STAGES) - set(OPTIONAL_STAGES)
TIMED = ONE_CHIP - {"source_wait", "tpusketch_inv_classes"}


class RecordingAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: like it, an annotation
    starts when it is made and ends at `__exit__`."""

    log: list[tuple[str, int, int, int]] = []   # name, thread, open, close

    def __init__(self, name: str):
        self.name = name
        self.thread = threading.get_ident()
        self.opened = time.perf_counter_ns()

    @staticmethod
    def is_enabled() -> bool:
        return True

    def __enter__(self):
        return self

    def __exit__(self, *_exc):
        self.log.append((self.name, self.thread, self.opened,
                         time.perf_counter_ns()))


def loop_annotations(run) -> list[tuple[int, int, str]]:
    """The loop thread's `ig:` annotations in time order: open, close,
    name."""
    return sorted((a, b, n) for n, th, a, b in run["annotations"]
                  if th == run["thread"] and n.startswith("ig:"))


def run_once(batches: int, history_dir: str):
    """One local `trace exec` run with the native synthetic source and
    history on, cancelled after `batches` batches. Returns the teardown
    summary's `pipeline` block and the batches the caller's tap saw."""
    desc = get("trace", "exec")
    params = desc.params().to_params()
    for k, v in (("source", "synthetic"), ("rate", "200000"),
                 ("batch-size", "1024"), ("vocab", "500")):
        params.set(k, v)
    sp = get_op("tpusketch").instance_params().to_params()
    for k, v in (("enable", "true"), ("depth", "2"), ("log2-width", "8"),
                 ("hll-p", "6"), ("entropy-log2-width", "6"), ("topk", "8"),
                 ("harvest-interval", "50ms"), ("history", "true"),
                 ("history-interval", "120ms"), ("history-log2-width", "6"),
                 ("history-dir", history_dir)):
        sp.set(k, v)
    ops = Collection()
    ops["operator.tpusketch."] = sp
    summaries: list = []
    seen = [0]

    def on_batch(_batch) -> None:
        seen[0] += 1
        if seen[0] >= batches:
            ctx.cancel()

    ctx = GadgetContext(desc, gadget_params=params, operator_params=ops,
                        timeout=60.0,
                        extra={"on_sketch_summary": summaries.append})
    try:
        result = LocalRuntime().run_gadget(ctx, on_batch=on_batch)
    finally:
        from inspektor_gadget_tpu.history import HISTORY
        HISTORY.close_all()
    assert not result.errors(), result.errors()
    return summaries[-1].pipeline, seen[0]


@pytest.fixture(scope="module")
def recorded_run():
    """One run under the recording annotation and an empty tracer ring."""
    saved = tracing._annotation_cls
    tracing._annotation_cls = RecordingAnnotation
    RecordingAnnotation.log = []
    TRACER.reset()
    before = snapshot()
    steps0 = before.get(STEPS, 0.0)
    try:
        with tempfile.TemporaryDirectory(prefix="turn-hist-") as d:
            pipe, batches = run_once(150, d)
    finally:
        tracing._annotation_cls = saved
    return {"pipe": pipe, "batches": batches, "before": before,
            "after": snapshot(),
            "steps": snapshot().get(STEPS, 0.0) - steps0,
            "annotations": list(RecordingAnnotation.log),
            "thread": threading.get_ident(),
            "spans": TRACER.records()}


def test_stages_tile_the_turn(recorded_run):
    turn = recorded_run["pipe"]["turn"]
    assert set(turn["stages"]) == ONE_CHIP
    for name in TIMED:
        assert turn["stages"][name] > 0.0, name
    assert turn["stages"]["tpusketch_inv_classes"] == 0.0
    assert 0.0 < turn["harvest_wait_s"] < turn["stages"]["tpusketch_harvest"]
    assert turn["turns"] == recorded_run["steps"] == recorded_run["batches"]
    # a stage's two clock reads stand right around its annotation (26-55
    # us a turn apart over the fourteen of them, on a busy host too), and
    # the turns' wall is the loop's span from its first stage to its last
    # publication. With the annotations' own cover of that span, held in
    # the next test, this is "the stages tile the turn". It was `stages
    # >= 0.95 * wall` while the slice loop made a turn 13 ms long; since
    # ISSUE 28 the glue between the stages is 4% and more of a 4-5 ms turn
    stages = sum(turn["stages"].values())
    loop = loop_annotations(recorded_run)
    last = max(b for _a, b, n in loop if n == "ig:runtime_deliver")
    covered = sum(b - a for a, b, _n in loop if b <= last) * 1e-9
    assert covered <= stages <= covered + 100e-6 * turn["turns"]
    assert stages <= turn["wall_s"] <= (last - loop[0][0]) * 1e-9 + 0.01


def test_annotations_are_siblings_that_cover_the_loop(recorded_run):
    loop = loop_annotations(recorded_run)
    # a loop that keeps up with its source also waits for it now and then
    assert ({n for _a, _b, n in loop} - {"ig:source_wait"}
            == {"ig:" + s for s in TIMED})
    # every turn ends in runtime_deliver (the tap): first to last turn
    ends = [b for _a, b, n in loop if n == "ig:runtime_deliver"]
    inside = [(a, b, n) for a, b, n in loop if ends[0] <= a and b <= ends[-1]]
    per_turn = [0]          # time between stages, turn by turn
    for (_a0, b0, n0), (a1, _b1, n1) in zip(inside, inside[1:]):
        assert a1 >= b0, f"{n1} opened inside {n0}"
        if n0 == "ig:runtime_deliver":
            per_turn.append(0)
        per_turn[-1] += a1 - b0
    # What lies between two stages is the loop's own glue, a fixed cost a
    # turn whatever the stages take (0.16-0.21 ms). It was held to 5% of
    # the loop's span while the slice loop made a turn 13 ms long, which
    # is 0.65 ms a turn; a turn is 4-5 ms since ISSUE 28. On a busy host
    # (seven of eight cores kept busy) a tenth of the turns also wait 3-8
    # ms where the turn is published, and the whole reads 0.33-0.8 ms a
    # turn; the other nine tenths read 0.18-0.26 ms a turn whatever the
    # host runs, and are held to 0.5 ms, so a stage of 0.3 ms left
    # without a name is seen unless it comes in fewer than a tenth of
    # the turns.
    kept = sorted(per_turn)[:len(per_turn) * 9 // 10]
    assert sum(kept) <= 500_000 * len(kept)


def test_a_run_without_the_scorer_carries_none_of_its_names(recorded_run):
    """`trace exec` records nothing of its own and the scorer is off: no
    optional stage, counted part, `pipeline` key, counter child or
    annotation of ISSUE 31 (or of shard-ingest) shows in the run."""
    pipe = recorded_run["pipe"]
    assert not set(OPTIONAL_STAGES) & set(pipe["turn"]["stages"])
    assert "anomaly" not in pipe and "anomaly_score_s" not in pipe["turn"]
    assert set(pipe["turn"]) == {"turns", "wall_s", "stages",
                                 "harvest_wait_s"}
    before, after = recorded_run["before"], recorded_run["after"]
    for key, value in after.items():
        mine = (any(f'stage="{s}"' in key
                    for s in (*OPTIONAL_STAGES, ANOMALY_SCORE))
                or (key.startswith("ig_tpusketch_anomaly")
                    and 'gadget="trace/exec"' in key))
        # another file's run in this process may have left such a child
        # behind; this run added nothing to it
        assert not mine or value == before.get(key), key
    named = {n for n, _th, _a, _b in recorded_run["annotations"]}
    assert not {"ig:" + s for s in OPTIONAL_STAGES} & named
    assert all(t["stages"].keys() <= ONE_CHIP | {HARVEST_WAIT}
               for t in pipe["slow_turns"])


def test_nothing_per_stage_reaches_the_tracer_ring(recorded_run):
    names = [r.name for r in recorded_run["spans"]]
    turns = recorded_run["pipe"]["turn"]["turns"]
    assert not [n for n in names
                if n.startswith("ig:") or n in TURN_STAGES
                or n == HARVEST_WAIT]
    # per batch what it was: one span an operator, and tpusketch's two
    per_batch = [n for n in names if n.startswith("op/")
                 or n in ("tpusketch/h2d", "tpusketch/update")]
    chain = {n for n in names if n.startswith("op/")}
    assert len(per_batch) == turns * (len(chain) + 2)
    rest = set(names) - set(per_batch)
    assert all(n.startswith(("run/", "tpusketch/harvest", "tpusketch/stage/",
                             "tpusketch/seal-window")) for n in rest), rest


def test_a_seal_books_its_capture_and_the_worker_times_nothing(recorded_run):
    """An interval-driven seal leaves `tpusketch_seal` the capture alone
    (and a wait, had there been one): a window's share of the stage stays
    under what its finish took on the worker, whose thread opens no `ig:`
    annotation and so no stage of the turn (ISSUE 33)."""
    pipe = recorded_run["pipe"]
    seal = pipe["seal"]         # the teardown's summary: before its seal
    assert seal["worker"] >= 2 and seal["caller"] == 0
    before, after = recorded_run["before"], recorded_run["after"]

    def moved(name: str) -> float:
        key = name + '{gadget="trace/exec"}'
        return after[key] - before.get(key, 0.0)

    windows = moved("ig_tpusketch_seal_finish_seconds_count")
    assert windows >= seal["worker"] + seal["pending"]
    finish = moved("ig_tpusketch_seal_finish_seconds_sum") / windows
    captures = seal["worker"] + seal["pending"]
    if not seal["waited"]:
        assert pipe["turn"]["stages"]["tpusketch_seal"] / captures < finish
    assert {th for n, th, _a, _b in recorded_run["annotations"]
            if n.startswith("ig:")} == {recorded_run["thread"]}
    sealed = [r for r in recorded_run["spans"]
              if r.name == "tpusketch/seal-window"]
    assert len(sealed) == windows


def test_a_slow_stage_leads_the_slowest_turn(monkeypatch):
    real = tpusketch.TpuSketchInstance._accumulate_slices
    calls = [0]

    def slow(self, *args):
        calls[0] += 1
        if calls[0] == 20:
            time.sleep(0.2)
        return real(self, *args)

    monkeypatch.setattr(tpusketch.TpuSketchInstance, "_accumulate_slices",
                        slow)
    with tempfile.TemporaryDirectory(prefix="turn-hist-") as d:
        pipe, batches = run_once(120, d)
    assert batches >= 100 and pipe["turn"]["turns"] >= 100
    slow_turns = pipe["slow_turns"]
    assert len(slow_turns) == SLOW_TURNS
    assert [t["wall_s"] for t in slow_turns] == sorted(
        (t["wall_s"] for t in slow_turns), reverse=True)
    first = slow_turns[0]
    assert first["seq"] == 20 and first["wall_s"] >= 0.2
    assert max(first["stages"], key=first["stages"].get) == "tpusketch_slices"
    assert first["cpu_s"] < first["wall_s"] / 4     # it slept: no CPU
    assert first["start"] <= time.time()


def test_slow_turns_keep_the_longest_four():
    stats = PipelineStats("run-turn-unit")
    ns = [0] * (len(TURN_STAGES) + 2)
    for seq, wall in enumerate([5, 1, 9, 3, 7, 2, 8, 4, 6], start=1):
        ns[1] = wall
        stats.note_turn(ns, wall, wall, float(seq), seq)
    snap = stats.snapshot()
    assert snap["turn"]["turns"] == 9
    assert snap["turn"]["wall_s"] == pytest.approx(45e-9)
    assert [t["seq"] for t in snap["slow_turns"]] == [3, 7, 5, 9]
    # the record is a copy taken when the turn entered the four
    assert snap["slow_turns"][0]["stages"] == {
        "source_pop": pytest.approx(9e-9)}


def test_the_helper_costs_under_two_microseconds():
    assert not tracing.annotation_class().is_enabled()   # no session
    stage = TurnClock().stage("source_pop")
    pairs, best = 100_000, float("inf")
    for _round in range(3):     # suite load: the best of three averages
        t0 = time.perf_counter()
        for _ in range(pairs):
            with stage:
                pass
        best = min(best, (time.perf_counter() - t0) / pairs)
    assert best < 2e-6, f"{best * 1e6:.2f} us a stage"


def test_compiles_are_counted():
    import jax
    import jax.numpy as jnp
    from inspektor_gadget_tpu.utils.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    ensure_compile_cache()      # idempotent: one listener, not two
    x = jnp.arange(7.0)
    fresh = jax.jit(lambda v: v * 3.0 + 1.0)

    def counts() -> tuple[float, float]:
        snap = snapshot()
        return (snap["ig_jax_backend_compiles_total"],
                snap["ig_jax_backend_compile_seconds_total"])

    n0, s0 = counts()
    fresh(x).block_until_ready()
    n1, s1 = counts()
    assert n1 == n0 + 1 and s1 > s0
    fresh(x).block_until_ready()
    assert counts() == (n1, s1)
    assert "ig_jax_compile_cache_hits_total" in snapshot()
