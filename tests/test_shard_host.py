"""The 4-chip host as a deployment (ISSUE 27): `trace exec` under
`shard-ingest true`, `chips 4` with history on, through the served path.

What is held here: a served run's answers and accounting lie inside the
configuration's envelopes against exact counts (chipbench/reference.py is
the plain reference), every harvested leaf and every sealed window's digest
equal the one-chip fold of the same stream (a), the counters of rounds,
filler lanes and events per lane count what happened (b), and a one-chip
run carries none of the new names (c). The four lanes are four of the
eight CPU devices tests/conftest.py forces.
"""

from __future__ import annotations

import copy
import importlib.util
import json
import tempfile
from pathlib import Path

import jax
import numpy as np
import pytest

import inspektor_gadget_tpu.all_gadgets  # noqa: F401
from inspektor_gadget_tpu.gadgets import GadgetContext, get
from inspektor_gadget_tpu.history import HISTORY
from inspektor_gadget_tpu.operators import tpusketch
from inspektor_gadget_tpu.operators.operators import get as get_op
from inspektor_gadget_tpu.params import Collection
from inspektor_gadget_tpu.runtime.local import LocalRuntime
from inspektor_gadget_tpu.sources.synthetic import PySyntheticSource
from inspektor_gadget_tpu.telemetry import snapshot
from inspektor_gadget_tpu.telemetry.pipeline import SHARD_STAGES

BENCH = Path(__file__).resolve().parents[1] / "chipbench"
SEED = 2700000027
BATCH = 2048
# the key vocabulary stays under the candidate table's size k: above it the
# table is an approximation on every path, the union at harvest can only
# widen the candidate pool, and the tail of the top-k may differ between
# four lanes and one (tests/test_sharded_ingest.py has the long form)
VOCAB = 200
GEOMETRY = {"depth": 4, "log2-width": 12, "hll-p": 10,
            "entropy-log2-width": 10, "topk": 256}
ROUNDS = "ig_tpusketch_shard_rounds_total"
FILLERS = "ig_tpusketch_shard_filler_lanes_total"
LANE_EVENTS = "ig_tpusketch_shard_lane_events_total"


def bench_module(name: str):
    """A module of the benchmark by path: chipbench/ is no package and its
    module names (`run`, `tap`) are too plain to put on sys.path here."""
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{name}", BENCH / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def operator_params(history_dir: str, **more: str):
    p = get_op("tpusketch").instance_params().to_params()
    for k, v in {"enable": "true", **{k: str(v) for k, v in GEOMETRY.items()},
                 "history": "true", "history-log2-width": "8",
                 "history-dir": history_dir, **more}.items():
        p.set(k, v)
    return p


def leaves_of(bundle) -> list[np.ndarray]:
    return [np.asarray(x) for x in jax.tree.leaves(bundle)]


def shard_names(snap: dict) -> dict:
    return {k: v for k, v in snap.items() if "shard" in k}


@pytest.fixture(scope="module")
def served():
    """One served run on four lanes, tapped as the benchmark taps it. Keeps
    what the replay needs: a copy of every batch, the batch each summary
    and each seal closed, and the merged state's leaves at every summary."""
    Tap = bench_module("tap").Tap
    desc = get("trace", "exec")
    params = desc.params().to_params()
    for k, v in (("source", "synthetic"), ("rate", "300000"),
                 ("seed", str(SEED)), ("batch-size", str(BATCH)),
                 ("vocab", str(VOCAB)), ("zipf", "1.2")):
        params.set(k, v)
    kept = {"batches": [], "leaves": [], "seal_marks": []}

    def on_summary(s) -> None:
        tap.on_summary(s)
        inst = tpusketch._live[ctx.run_id]
        with inst._bundle_mu:
            kept["leaves"].append(leaves_of(inst._merged_locked()))

    # a window is announced from the seal worker, some batches after the
    # boundary that closed it: the boundary is where the capture was taken
    capture = tpusketch.TpuSketchInstance._capture_window

    def marked_capture(inst):
        cap = capture(inst)
        if cap is not None:
            kept["seal_marks"].append(tap.batches)
        return cap

    def on_batch(batch) -> None:
        kept["batches"].append(copy.deepcopy(batch))
        tap.on_batch(batch)

    with tempfile.TemporaryDirectory(prefix="shard-host-") as d:
        ops = Collection()
        ops["operator.tpusketch."] = operator_params(
            d, **{"shard-ingest": "true", "chips": "4",
                  "harvest-interval": "100ms", "history-interval": "300ms"})
        ctx = GadgetContext(desc, gadget_params=params, operator_params=ops,
                            timeout=120.0,
                            extra={"on_sketch_summary": on_summary,
                                   "on_window_sealed":
                                       lambda header: tap.on_sealed(header)})
        tap = Tap(seconds=1.5, capacity_events=1 << 21,
                  capacity_batches=1 << 12, cancel=ctx.cancel,
                  snapshot=snapshot)
        try:
            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(tpusketch.TpuSketchInstance, "_capture_window",
                           marked_capture)
                result = LocalRuntime().run_gadget(ctx, on_batch=on_batch)
        finally:
            HISTORY.close_all()
    assert not result.errors(), result.errors()
    assert tap.overflow is None and tap.window_end is not None
    return {"tap": tap, **kept}


def test_served_sharded_run_answers_within_the_envelopes(served):
    """Heavy hitters, distinct, entropy, the accounting of every summary
    and the sealed windows' event sums, against exact counts of the
    tapped stream, by the limits exec-host4's file states."""
    tap = served["tap"]
    limits = json.loads(
        (BENCH / "configs" / "exec-host4.json").read_text())["limits"]
    correct, compared = bench_module("reference").compare(
        tap, seed=SEED, geometry=GEOMETRY, limits=limits, seal_failures=0.0)
    assert correct, compared
    assert compared["summaries_in_window"]["value"] >= 5
    assert compared["answers_checked"]["value"] >= 5
    assert len(tap.sealed) >= 3
    pipe = tap.summaries[-1][2].pipeline
    assert set(SHARD_STAGES) <= set(pipe["turn"]["stages"])
    assert all(pipe["turn"]["stages"][s] > 0.0 for s in SHARD_STAGES)
    shard = pipe["shard"]
    assert shard["rounds_full"] + shard["rounds_flushed"] == pipe["rounds"]
    assert sum(shard["lane_events"]) == tap.events


def test_served_sharded_run_equals_the_one_chip_fold(served):
    """The same batches through a one-chip instance, sealed and harvested
    at the batch boundaries the served run sealed and harvested at: every
    summary, every leaf of the state at every summary and every sealed
    window's digest come out equal."""
    tap, batches = served["tap"], served["batches"]
    summaries, sealed, leaves = [], [], []
    desc = get("trace", "exec")
    ctx = GadgetContext(desc, extra={"on_sketch_summary": summaries.append,
                                     "on_window_sealed": sealed.append})
    ctx.gadget_params.set("batch-size", str(BATCH))
    harvest_at = {b for _t, b, _s in tap.summaries if b < len(batches)}
    seal_at = {b for b in served["seal_marks"] if b < len(batches)}
    with tempfile.TemporaryDirectory(prefix="shard-host-one-") as d:
        inst = get_op("tpusketch").instantiate(ctx, None, operator_params(
            d, **{"harvest-interval": "1h", "history-interval": "1h"}))
        try:
            for i, batch in enumerate(batches):
                inst.enrich_batch(batch)
                if i in harvest_at:
                    inst.harvest()
                    with inst._bundle_mu:
                        leaves.append(leaves_of(inst.bundle))
                if i in seal_at:
                    inst.seal_window()
            inst.post_gadget_run()      # the teardown harvest and seal
            with inst._bundle_mu:
                leaves.append(leaves_of(inst.bundle))
        finally:
            HISTORY.close_all()
    assert inst.device_view()["lanes"] == 1
    assert len(summaries) == len(tap.summaries)
    for got, (_t, _b, want) in zip(summaries, tap.summaries):
        assert (got.events, got.drops, got.distinct, got.entropy_bits) == (
            want.events, want.drops, want.distinct, want.entropy_bits)
        assert got.heavy_hitters == want.heavy_hitters
    assert len(leaves) == len(served["leaves"])
    for n, (one, four) in enumerate(zip(leaves, served["leaves"])):
        assert len(one) == len(four)
        for a, b in zip(one, four):
            assert np.array_equal(a, b), f"a leaf diverged at summary {n}"
    assert ([(h["window"], h["events"], h["drops"], h["digest"])
             for h in sealed]
            == [(h["window"], h["events"], h["drops"], h["digest"])
                for _t, h in tap.sealed])


@pytest.fixture()
def six_batches():
    src = PySyntheticSource(seed=5, vocab=40, batch_size=512)
    return [src.generate(512) for _ in range(6)]


def instance(**more: str):
    p = get_op("tpusketch").instance_params().to_params()
    # no harvest but the test's own: a timer harvest would flush a round
    for k, v in {"enable": "true", "log2-width": "8", "hll-p": "6",
                 "entropy-log2-width": "6", "topk": "64",
                 "harvest-interval": "1h", **more}.items():
        p.set(k, v)
    return get_op("tpusketch").instantiate(
        GadgetContext(get("trace", "exec")), None, p)


def test_rounds_fillers_and_lane_events_are_counted(six_batches):
    """Six batches over four lanes and one harvest: one full round, one
    flushed round of two batches and two filler lanes, and lane events
    that add up to the events absorbed, in the registry and mirrored in
    the summary's `pipeline` block."""
    before = snapshot()
    inst = instance(**{"shard-ingest": "true", "chips": "4"})
    try:
        for b in six_batches:
            inst.enrich_batch(b)
        summary = inst.harvest()
    finally:
        inst.post_gadget_run()
    after = snapshot()

    def moved(name: str) -> dict:
        return {k: after[k] - before.get(k, 0.0) for k in after
                if k.startswith(name + "{")}

    g = 'gadget="trace/exec"'
    assert moved(ROUNDS) == {f'{ROUNDS}{{{g},kind="full"}}': 1.0,
                             f'{ROUNDS}{{{g},kind="flushed"}}': 1.0}
    assert moved(FILLERS) == {f"{FILLERS}{{{g}}}": 2.0}
    # lanes past the fourth may be there from an earlier run: none moved
    lanes = {k: v for k, v in moved(LANE_EVENTS).items() if v}
    assert sorted(lanes) == [f'{LANE_EVENTS}{{{g},lane="{k}"}}'
                             for k in range(4)]
    events = sum(b.count for b in six_batches)
    assert sum(lanes.values()) == events == summary.events
    # lanes 0 and 1 took two batches each, lanes 2 and 3 one
    per_lane = [lanes[f'{LANE_EVENTS}{{{g},lane="{k}"}}'] for k in range(4)]
    assert per_lane == [six_batches[k].count + (six_batches[k + 4].count
                                                if k < 2 else 0)
                        for k in range(4)]
    assert summary.pipeline["rounds"] == 2
    assert summary.pipeline["shard"] == {
        "rounds_full": 1, "rounds_flushed": 1, "filler_lanes": 2,
        "lane_events": [int(v) for v in per_lane]}


def test_a_one_chip_run_carries_none_of_the_names(six_batches):
    """With `shard-ingest` off no new counter comes into being or moves,
    and the `pipeline` block has neither the `shard` key nor the
    sharding-only stages."""
    before = snapshot()
    inst = instance(history="true", **{"history-interval": "0"})
    try:
        for b in six_batches:
            inst.enrich_batch(b)
        summary = inst.harvest()
    finally:
        inst.post_gadget_run()
        HISTORY.close_all()
    assert shard_names(snapshot()) == shard_names(before)
    assert summary.events == sum(b.count for b in six_batches)
    assert "shard" not in summary.pipeline
    assert summary.pipeline["rounds"] == 0
    assert not set(SHARD_STAGES) & set(summary.pipeline["turn"]["stages"])
