"""Tests of the benchmark itself: pytest chipbench/tests -q   (CPU, ~1 min)

They run the harness's CPU rehearsal (tiny sizes, names suffixed
`.cpu_rehearsal`), never a chip; the control and every fault the one-chip
cells can have are planted under a whole rehearsal and must come out as
not correct.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import types
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT)]

import reference  # noqa: E402
import tracereduce  # noqa: E402
import work  # noqa: E402
from tap import Tap  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


def bench(script: str, *args: str) -> subprocess.CompletedProcess:
    return subprocess.run([sys.executable, str(BENCH / script), *args],
                          cwd=ROOT, env=ENV, capture_output=True, text=True,
                          timeout=300)


def test_rehearsal_ends_in_the_contracts_line():
    out = bench("run.py", "--workload", "exec-node.saturate", "--seed",
                "3000000019", "--seconds", "2", "--trace", "0",
                "--platform", "cpu")
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared"
    assert line["correct"] is True and line["attempted"] > 0
    assert set(line["metrics"]) == {"events_per_s.cpu_rehearsal",
                                    "setup_s.cpu_rehearsal"}
    assert line["device"]["platform"] == "cpu"
    assert "correct = True" in out.stderr.strip().splitlines()[-1]
    for row in line["compared"].values():
        assert set(row) == {"value", "limit"}


def test_no_chip_no_number():
    out = bench("run.py", "--workload", "exec-node.saturate", "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert out.returncode != 0
    assert '"correct"' not in out.stdout


@pytest.fixture(scope="module")
def control_lines():
    out = bench("control.py", "--workload", "exec-node.saturate", "--seeds",
                "7", "--seconds", "2", "--faults",
                "shed,unchanged,half,altered,dropped,narrow", "--platform", "cpu")
    assert out.returncode == 0, out.stderr[-2000:]
    rows = [json.loads(ln) for ln in out.stdout.splitlines()
            if ln.startswith('{"fault"')]
    return {r["fault"]: r for r in rows}


@pytest.mark.parametrize("fault,caught_by", [
    ("shed", "events_gap"), ("unchanged", "events_gap"),
    ("half", "hh_under"), ("altered", "hh_under"),
    ("dropped", "hh_missing"), ("narrow", "entropy_gap_bits")])
def test_a_broken_timed_path_is_not_correct(control_lines, fault, caught_by):
    assert control_lines["none"]["correct"] is True
    row = control_lines[fault]
    assert row["correct"] is False
    value, limit = row["compared"][caught_by]
    assert value > limit


def test_trace_reduction_on_the_recorded_slice():
    from jax.profiler import ProfileData
    text = (BENCH / "tests/data/trace_slice.txt").read_text()
    r = tracereduce.reduce_trace(
        tracereduce.planes_of(ProfileData.from_text_proto(text)))
    # 0.4 s cut from a traced exec-node.saturate run on a TPU v5 lite (PR 23)
    assert r["window_s"] == pytest.approx(0.4)
    assert r["chips"] == 1 and r["harvests"] == 1
    assert r["busy_s"] == pytest.approx(0.341843414, rel=1e-6)
    seconds, runs = r["programs"]["jit_bundle_ingest_step"]
    assert runs == 8 and seconds == pytest.approx(0.323714926, rel=1e-6)
    assert {"jit__wcms_ingest_step", "jit__hll_ingest_step"} <= set(
        r["programs"])
    assert r["device_ops"][0][0] == (
        "jit_bundle_ingest_step/fused_sketch_planes.1")
    assert r["device_ops"][0][1] == pytest.approx(0.293144489, rel=1e-6)
    idle = dict(r["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(0.4 - r["busy_s"], rel=1e-6)


def test_trace_arithmetic():
    assert tracereduce.union_seconds([(0, 2e9), (1e9, 3e9), (5e9, 6e9)]) == 4.0
    assert tracereduce.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert tracereduce.short_name(
        "jit_f(123)", "%fusion.2 = s32[8]{0} fusion(...)") == "jit_f/fusion.2"


def test_work_of_the_default_geometry():
    w = work.update_work(batch=65536, lanes=2, depth=4, topk=128)
    # 2 lanes in: 524288; 6 counters an event, read + write: 3145728; table 2048
    assert w == {"bytes": 524288 + 3145728 + 2048, "ops": 393216}
    least, binds = work.least_seconds(w, work.peaks("TPU v5 lite"))
    assert binds == "bytes" and least == pytest.approx(3672064 / 819e9)
    with pytest.raises(KeyError):
        work.peaks("TPU v9")


def summary(keys, counts, distinct, entropy):
    return types.SimpleNamespace(heavy_hitters=list(zip(keys, counts)),
                                 distinct=distinct, entropy_bits=entropy)


def test_reference_judges_answers():
    rng = np.random.default_rng(5)
    keys = rng.zipf(1.3, 200_000).astype(np.uint32) % 5000 + 1
    exact = reference.Exact(keys)
    counts = exact.counts(len(keys))
    top = np.argsort(-counts)[:32]
    geometry = {"depth": 4, "log2-width": 16, "hll-p": 14,
                "entropy-log2-width": 12, "topk": 32}
    limits = {"entropy_gap_bits": 0.06}
    # what a sound 4096-bucket histogram reads: the keys through ANOTHER hash
    buckets = (reference._mix(exact.uniq, 99) >> np.uint64(52)).astype(int)
    collapsed = np.bincount(buckets, weights=counts, minlength=4096)
    good = summary(exact.uniq[top].tolist(), counts[top].tolist(),
                   float((counts > 0).sum()), reference.entropy_bits(collapsed))
    rows = reference.check_answers(good, exact, len(keys), geometry, limits)
    assert all(lim is None or v <= lim for v, lim in rows.values()), rows
    assert rows["entropy_err_bits"][0] > 0.06        # against the exact: shown
    under = summary(exact.uniq[top].tolist(), (counts[top] - 3).tolist(),
                    good.distinct * 1.05, good.entropy_bits)
    rows = reference.check_answers(under, exact, len(keys), geometry, limits)
    assert rows["hh_under"] == (3.0, 0.0)
    assert rows["distinct_err"][0] > rows["distinct_err"][1]
    # over-counts (the fault `inflated`) and a histogram half as wide (`narrow`)
    half_wide = np.bincount(buckets % 2048, weights=counts, minlength=4096)
    over = summary(exact.uniq[top].tolist(), (counts[top] * 1.01 + 9).tolist(),
                   good.distinct, reference.entropy_bits(half_wide))
    rows = reference.check_answers(over, exact, len(keys), geometry, limits)
    assert rows["hh_under"][0] == 0.0
    assert rows["hh_beyond_bound"][0] > rows["hh_beyond_bound"][1]
    assert rows["entropy_gap_bits"][0] > 3 * 0.06
    # a prefix is counted on its own, in either order
    assert exact.counts(100_000).sum() == 100_000
    assert exact.counts(50_000).sum() == 50_000


def test_operations_count_what_was_due():
    import run as harness
    tap = types.SimpleNamespace(
        window_start=100.0, window_end=104.0, first_batch=1, batches=50,
        drops=np.zeros(50, np.int64),
        # summaries every 0.29 s, one 0.55 s late (a seal in the same turn:
        # sound), then a stall of 1.1 s, then on again
        window_summaries=lambda: [(100.0 + t, 10, None) for t in (
            0.29, 0.84, 1.13, 2.23, 2.52, 2.81, 3.10, 3.39, 3.68, 3.97)],
        sealed=[(101.1, {}), (102.2, {}), (103.3, {}), (99.0, {})])
    config = {"operator": {"harvest-interval": "250ms",
                           "history-interval": "1s"}}
    attempted, failed, cadence = harness.operations(tap, config, "saturate", 0)
    assert (attempted, failed) == (10 + 3 + 2, 2)     # 1.1 s: two went missing
    assert cadence["summary_gap_max_s"] == pytest.approx(1.1)
    tap.drops[10:] = 7                                # the ring shed: paced only
    assert harness.operations(tap, config, "saturate", 0)[1] == 2
    assert harness.operations(tap, config, "paced", 1)[1] == 2 + 1 + 1


def test_tap_window_fold_and_lag_pairing():
    cancelled = []
    tap = Tap(seconds=0.05, capacity_events=64, capacity_batches=8,
              cancel=lambda: cancelled.append(1), snapshot=dict)

    def batch(keys, ts, drops):
        cols = {"key_hash": np.array(keys, np.uint64),
                "ts": np.array(ts, np.uint64)}
        return types.SimpleNamespace(count=len(keys), cols=cols, drops=drops)

    import time
    now_ns = time.time_ns()
    tap.on_batch(batch([(7 << 32) | 5, 1], [1, 2], 0))      # before the window
    # a harvest runs inside enrich_batch(k); the tap for k fires after it
    tap.on_summary("opens")                                 # in batch 1
    tap.on_batch(batch([3], [3], 0))                        # batch 1: before
    tap.on_batch(batch([8], [4], 0))                        # batch 2: inside
    assert not cancelled
    tap.on_summary("inside")                                # in batch 3
    time.sleep(0.06)
    tap.on_batch(batch([4, 4], [now_ns - 50_000_000, 9], 2))
    assert tap.keys[:6].tolist() == [7 ^ 5, 1, 3, 8, 4, 4]
    assert cancelled and tap.first_batch == 2 and tap.last_batch == 4
    assert tap.absorbed(tap.first_batch, tap.last_batch) == 3
    assert [s for _t, _b, s in tap.window_summaries()] == ["inside"]
    (lag,) = tap.lags_ms()
    assert 50.0 <= lag < 1000.0
    # the end-to-end readers take the same integers and wall times
    from readers import window
    run = types.SimpleNamespace(tap=tap, setup_s=1.5)
    assert window.events_per_s(run) == pytest.approx(
        3 / (tap.window_end - tap.window_start))
    assert window.lag_ms(run, 0.95) is None     # one summary: no percentile
    assert window.setup_s(run) == 1.5
    assert 0.0 < window.summary_gap_max_ms(run) < 1000.0


def test_benchmark_json_names_files_that_exist():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert b["command"] == ["python3", "chipbench/run.py"]
    assert b["paths"] == ["chipbench"]
    cells = {w["name"]: w for w in b["workloads"]}
    for c in b["configs"]:
        assert NAME.match(c["name"]) and c["file"] == (
            f"chipbench/configs/{c['name']}.json")
        on_disk = json.loads((ROOT / c["file"]).read_text())
        assert on_disk["source"] == c["source"] and len(c["source"]) <= 200
        assert on_disk["reduced"] == c["reduced"]
    for name, w in cells.items():
        cell = json.loads((BENCH / "workloads" / f"{name}.json").read_text())
        assert NAME.match(name) and NAME.match(w["traffic"])
        assert (cell["config"], cell["traffic"], cell["chips"]) == (
            w["config"], w["traffic"], w["chips"])
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert cell["why"] == w["why"] and len(w["why"]) <= 200
        assert set(cell["end_to_end"]) <= {m["name"] for m in b["end_to_end"]}
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["end_to_end"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert 0.01 <= m["bound"] <= 0.25
        on_disk = json.loads(
            (BENCH / "end_to_end" / f"{m['name']}.json").read_text())
        for key in ("unit", "better", "bound", "source"):
            assert on_disk[key] == m[key], (m["name"], key)
    sys.path.insert(0, str(BENCH))
    import run as harness
    for m in b["per_layer"]:
        on_disk = json.loads(
            (BENCH / "metrics" / f"{m['name']}.json").read_text())
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["moves"] in e2e
        for key in ("unit", "better", "source", "layer", "moves"):
            assert on_disk[key] == m[key], (m["name"], key)
        reports = {
            name for name in cells
            if any(x["name"] == m["name"] for x in harness.metrics_for(
                *harness.load_cell(name, "tpu")[::2]))}
        assert reports == set(m.get("workloads", cells)), m["name"]
