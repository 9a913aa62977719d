"""The staged shape of a batch follows what the batch holds (ISSUE 30).

On one chip `enrich_batch` and `ingest_folded` stage and step a batch at
the smallest power of two of rows that holds it, from `STEP_ROWS_FLOOR` up
to the pad (`_step_rows`); a pad row is key 0 at weight 0 and changes no
leaf, so the state must be bit-identical to the one every batch at the full
pad leaves. Every size of the ladder is compiled by `pre_gadget_run`, ahead
of the source; sharded rounds keep the pad.
"""

from __future__ import annotations

from collections import Counter

import jax
import numpy as np
import pytest

import inspektor_gadget_tpu.all_gadgets  # noqa: F401
from inspektor_gadget_tpu.gadgets import GadgetContext, get
from inspektor_gadget_tpu.operators import tpusketch
from inspektor_gadget_tpu.operators.operators import get as get_op
from inspektor_gadget_tpu.sources.batch import EventBatch, FoldedBatch
from inspektor_gadget_tpu.telemetry import snapshot
from inspektor_gadget_tpu.utils.compile_cache import ensure_compile_cache

FLOOR = tpusketch.STEP_ROWS_FLOOR
PAD = 4 * FLOOR          # a ladder of three sizes: FLOOR, 2 FLOOR, 4 FLOOR
G = 'gadget="trace/exec"'
HISTORY = {"history": "true", "history-interval": "0"}
PLANES = {
    "history": HISTORY,
    "planes": {**HISTORY, "quantiles": "true", "invertible": "true",
               "priority-classes": "hot=6:101|102,rest=6:*"},
}


@pytest.fixture(autouse=True)
def _release_instances():
    """Instances built outside a gadget run: out of the live table, their
    stagers drained, so nothing leaks into other test files."""
    before = set(tpusketch._live)
    yield
    with tpusketch._live_mu:
        fresh = [rid for rid in list(tpusketch._live) if rid not in before]
        insts = [tpusketch._live.pop(rid) for rid in fresh]
    for inst in insts:
        for st in [inst._stager, *inst._lane_stagers]:
            if st is not None:
                st.drain()
        inst._stats.unregister()
        inst._pstats.unregister()


def make_instance(params: dict, tmp_path, batch_size: int = PAD):
    ctx = GadgetContext(get("trace", "exec"))
    ctx.gadget_params.set("batch-size", str(batch_size))
    p = get_op("tpusketch").instance_params().to_params()
    for k, v in {"enable": "true", "log2-width": "8", "hll-p": "6",
                 "entropy-log2-width": "6", "topk": "64",
                 "history-log2-width": "6", "harvest-interval": "1h",
                 "history-dir": str(tmp_path), **params}.items():
        p.set(k, v)
    return get_op("tpusketch").instantiate(ctx, None, p)


def stream_of(count: int, rng) -> list[tuple]:
    """(keys, mntns, values, cumulative drops) per batch: `count` events,
    a short batch that lands on the floor, `count` again. More distinct
    keys than the top-k holds, three tenants, magnitudes with zeros."""
    out, drops = [], 0
    for i, n in enumerate((count, 300, count)):
        drops += i * 7
        out.append((rng.integers(1, 5000, n).astype(np.uint32),
                    rng.choice([101, 102, 777], n).astype(np.uint32),
                    rng.integers(0, 1 << 20, n).astype(np.uint32), drops))
    return out


def feed(inst, entry: str, keys, mntns, values, drops: int = 0,
         capacity: int = PAD) -> None:
    n = len(keys)
    if entry == "folded":
        block = inst.folded_block()
        block[0][:n], block[1][:n], block[2][:n] = keys, 1, mntns
        block[3][:n] = values
        inst.ingest_folded(FoldedBatch(lanes=block, count=n, drops=drops,
                                       has_values=inst._qt_on))
        return
    b = EventBatch.alloc(capacity, with_comm=False)
    b.cols["key_hash"][:n] = keys      # under 2^32: folds to itself
    b.cols["mntns"][:n] = mntns
    b.cols["aux1"][:n] = values
    b.cols["ts"][:n] = 1
    b.count, b.drops = n, drops
    inst.enrich_batch(b)


def feed_counts(inst, counts, entry: str = "batch", capacity: int = PAD):
    for n in counts:
        keys = (np.arange(n, dtype=np.uint32) % 4999) + 1
        feed(inst, entry, keys, np.full(n, 101, np.uint32),
             np.ones(n, np.uint32), capacity=capacity)


def leaves(tree) -> list:
    return [(jax.tree_util.keystr(path), np.asarray(leaf))
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)]


def state_of(inst) -> dict:
    """Every leaf the dispatch writes: the bundle, the window CMS and
    HLL, the class sketches."""
    with inst._bundle_mu:
        return {"bundle": leaves(inst._merged_locked()),
                "window": leaves((inst._wcms, inst._win_hll)),
                "classes": leaves([s for _, s in inst._inv_classes])}


def counters() -> dict:
    snap = snapshot()
    return {k: snap.get(k, 0.0) for k in (
        f"ig_tpusketch_steps_total{{{G}}}",
        f"ig_tpusketch_events_total{{{G}}}",
        f"ig_tpusketch_step_rows_total{{{G}}}",
        f'ig_tpusketch_update_arm_steps_total{{{G},arm="scatter"}}')}


def compiles() -> float:
    return snapshot()["ig_jax_backend_compiles_total"]


# -- the rule ---------------------------------------------------------------

@pytest.mark.parametrize("n, rows", [
    (1, FLOOR), (FLOOR - 1, FLOOR), (FLOOR, FLOOR), (FLOOR + 1, 2 * FLOOR),
    (2 * FLOOR, 2 * FLOOR), (2 * FLOOR + 1, PAD), (PAD - 1, PAD), (PAD, PAD)])
def test_rows_are_the_batchs_power_of_two_from_the_floor(n, rows, tmp_path):
    inst = make_instance({}, tmp_path)
    assert inst._step_rows(n, PAD) == rows
    # a block smaller than the floor (a default deployment's is the floor)
    assert inst._step_rows(min(n, 512), 512) == 512


def test_the_default_batch_size_has_one_program(tmp_path):
    """The gadget's documented default batch-size is the floor: every
    batch of a default deployment runs the one shape it always ran."""
    desc = get("trace", "exec")
    default = desc.params().to_params().get("batch-size").as_int()
    assert default == FLOOR
    inst = make_instance({}, tmp_path, batch_size=default)
    assert {inst._step_rows(n, inst._pad) for n in (1, 100, FLOOR)} == {FLOOR}


# -- (a) the state is the full pad's ----------------------------------------

@pytest.mark.parametrize("entry", ["batch", "folded"])
@pytest.mark.parametrize("planes", PLANES)
@pytest.mark.parametrize("count", [1, FLOOR - 1, FLOOR, FLOOR + 1, PAD - 1,
                                   PAD])
def test_ladder_leaves_the_state_the_full_pad_leaves(count, planes, entry,
                                                     tmp_path, monkeypatch):
    """The same stream stepped at the ladder's sizes and with every batch
    at the full pad: every leaf of the bundle, of the window CMS and HLL
    and of the class sketches bit-identical, through either adapter."""
    stream = stream_of(count, np.random.default_rng(30))

    def run(name: str) -> tuple[dict, dict]:
        inst = make_instance(PLANES[planes], tmp_path / name)
        for batch in stream:
            feed(inst, entry, *batch)
        got = state_of(inst)
        stepped = dict(inst._steps_by_rows)
        inst.post_gadget_run()
        return got, stepped

    got, stepped = run("ladder")
    with monkeypatch.context() as m:
        m.setattr(tpusketch.TpuSketchInstance, "_step_rows",
                  lambda self, n, cap: cap)
        want, padded = run("pad")
    assert padded == {PAD: 3}
    assert stepped == dict(Counter(
        max(FLOOR, 1 << (len(b[0]) - 1).bit_length()) for b in stream))
    np.testing.assert_equal(got, want)


# -- (b) priming ------------------------------------------------------------

def test_priming_leaves_a_resumed_state_and_every_count(tmp_path):
    """`pre_gadget_run` on a state resumed from a checkpoint: every leaf,
    the top-k's counts, `events` and `drops` as they were, no step, event,
    row or arm counted."""
    tpusketch.set_checkpoint_dir(tmp_path / "ckpt")
    try:
        first = make_instance(PLANES["planes"], tmp_path / "a")
        for batch in stream_of(FLOOR + 1, np.random.default_rng(31)):
            feed(first, "batch", *batch)
        saved = first.harvest()
        first.post_gadget_run()                    # checkpoints
        inst = make_instance(PLANES["planes"], tmp_path / "b")
    finally:
        tpusketch.set_checkpoint_dir(None)
    resumed = inst.harvest()
    assert resumed.events == saved.events > 0
    assert resumed.heavy_hitters == saved.heavy_hitters
    # the window planes are per run: give them something to keep too
    feed_counts(inst, [700])
    before, counted = state_of(inst), counters()
    inst.pre_gadget_run()
    np.testing.assert_equal(state_of(inst), before)
    assert counters() == counted
    after = inst.harvest()
    assert (after.events, after.drops) == (saved.events + 700, saved.drops)
    assert after.pipeline["step_rows"] == {str(FLOOR): 1}
    inst.post_gadget_run()


def test_the_runtime_primes_before_the_source_and_off_the_timeout(
        tmp_path, monkeypatch):
    """LocalRuntime calls `pre_gadget_run` ahead of the gadget's run, and
    the run's timeout starts after it: a run shorter than the priming's
    compiles still absorbs its events."""
    from inspektor_gadget_tpu.params import Collection
    from inspektor_gadget_tpu.runtime.local import LocalRuntime

    order: list[str] = []
    cls = tpusketch.TpuSketchInstance
    prime, absorb = cls.pre_gadget_run, cls.enrich_batch

    def slow_prime(self):
        import time
        order.append("prime")
        prime(self)
        time.sleep(0.6)         # longer than the timeout below

    def noted_absorb(self, batch):
        order.append("batch")
        absorb(self, batch)

    monkeypatch.setattr(cls, "pre_gadget_run", slow_prime)
    monkeypatch.setattr(cls, "enrich_batch", noted_absorb)
    desc = get("trace", "exec")
    params = desc.params().to_params()
    for k, v in (("source", "pysynthetic"), ("rate", "100000"),
                 ("batch-size", str(2 * FLOOR))):
        params.set(k, v)
    sp = get_op("tpusketch").instance_params().to_params()
    for k, v in {"enable": "true", "log2-width": "8", "hll-p": "6",
                 "entropy-log2-width": "6", "topk": "16",
                 "harvest-interval": "100ms"}.items():
        sp.set(k, v)
    ops = Collection()
    ops["operator.tpusketch."] = sp
    summaries: list = []
    ctx = GadgetContext(desc, gadget_params=params, operator_params=ops,
                        timeout=0.5,
                        extra={"on_sketch_summary": summaries.append})
    result = LocalRuntime().run_gadget(ctx)
    assert not result.errors(), result.errors()
    assert order[0] == "prime" and order.count("prime") == 1
    assert "batch" in order and summaries[-1].events > 0


# -- (c) nothing compiles once the run has started --------------------------

def test_no_size_up_to_the_pad_compiles_after_priming(tmp_path):
    ensure_compile_cache()      # starts the compile counter
    inst = make_instance(PLANES["planes"], tmp_path)
    inst.pre_gadget_run()
    primed = compiles()
    counts = [1, FLOOR - 1, FLOOR, FLOOR + 1, 2 * FLOOR, 2 * FLOOR + 1,
              PAD - 1, PAD]
    feed_counts(inst, counts)
    feed_counts(inst, counts[::3], entry="folded")
    assert compiles() == primed
    assert set(inst._steps_by_rows) == {FLOOR, 2 * FLOOR, PAD}
    # priming again (the measured run after a warm-up run) compiles nothing
    inst.pre_gadget_run()
    assert compiles() == primed
    # a batch over the pad still ratchets the pad (and compiles where it
    # lands, unless an earlier test of this process met the shape)
    feed_counts(inst, [PAD + 1], capacity=2 * PAD)
    assert inst._pad == 2 * PAD
    assert inst._steps_by_rows[2 * PAD] == 1
    assert inst.device_view()["step"][1][1].shape == (2 * PAD,)
    ratcheted = compiles()
    feed_counts(inst, [PAD + 2, 5], capacity=2 * PAD)
    assert compiles() == ratcheted
    inst.post_gadget_run()


# -- (d) sharded rounds stay rectangular ------------------------------------

def test_sharded_rounds_keep_the_pad(tmp_path):
    ensure_compile_cache()
    inst = make_instance({**HISTORY, "shard-ingest": "true", "chips": "4"},
                         tmp_path)
    c0 = compiles()
    inst.pre_gadget_run()       # one shape: it compiles with the first round
    assert compiles() == c0 and inst._steps_by_rows == {}
    feed_counts(inst, [100, FLOOR + 1])
    assert {a.shape for p in inst._pending.values()
            for a in p["arrays"]} == {(PAD,)}
    feed_counts(inst, [7, PAD])
    view = inst.device_view()
    step, args = view["step"]
    assert view["lanes"] == 4
    assert [a.shape for a in args[1:5]] == [(4, PAD)] * 4
    summary = inst.harvest()
    assert summary.events == 100 + FLOOR + 1 + 7 + PAD
    assert summary.pipeline["step_rows"] == {str(PAD): 4}
    assert summary.pipeline["shard"]["rounds_full"] == 1
    inst.post_gadget_run()


# -- (e) the counter and the summary add up ---------------------------------

@pytest.mark.parametrize("entry", ["batch", "folded"])
def test_step_rows_add_up_to_what_was_dispatched(entry, tmp_path):
    inst = make_instance(HISTORY, tmp_path)
    inst.pre_gadget_run()       # counted nowhere
    counts = [3, FLOOR, FLOOR + 1, 2 * FLOOR, PAD - 1, 9, PAD, FLOOR + 2]
    rows = [max(FLOOR, 1 << (n - 1).bit_length()) for n in counts]
    c0 = counters()
    feed_counts(inst, counts, entry=entry)
    delta = {k: v - c0[k] for k, v in counters().items()}
    assert list(delta.values()) == [len(counts), sum(counts), sum(rows),
                                    len(counts)]
    pipe = inst.harvest().pipeline
    assert pipe["step_rows"] == {str(r): c
                                 for r, c in sorted(Counter(rows).items())}
    assert sum(int(r) * c for r, c in pipe["step_rows"].items()) == sum(rows)
    assert pipe["update_arm"] == "scatter"
    inst.post_gadget_run()
