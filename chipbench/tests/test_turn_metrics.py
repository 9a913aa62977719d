"""Tests of the turn metrics (readers/turn.py): pytest chipbench/tests -q

The CPU rehearsal of a `--trace 1` run: the profiler runs, the program's
counters are read as on the chip, and only the reduction of the device
trace is stood in for (a CPU trace has no device plane to reduce). Every
name ends in `.cpu_rehearsal`; none is a device number.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}

NEW = {"turn_host_ms_per_batch", "turn_accounted_share",
       "pop_host_ms_per_batch", "source_wait_ms_per_batch",
       "chain_other_host_ms_per_batch", "fold_host_ms_per_batch",
       "stager_stall_ms_per_batch", "update_dispatch_host_ms_per_batch",
       "window_planes_host_ms_per_batch", "slices_host_ms_per_batch",
       "seal_host_ms", "harvest_wait_ms", "turn_max_ms",
       "turn_max_cpu_share", "window_compiles"}
BY_MODE = {"seal_host_ms", "harvest_wait_ms", "turn_max_ms"}

# run.py's main() with the trace reduction stood in for: nothing else of
# the harness is touched, so the window, the tap and the readers are its own
REHEARSAL = """
import sys
sys.path[:0] = [{bench!r}, {root!r}]
import run as harness
harness.trace_reduction.load = lambda path: []
harness.trace_reduction.reduce_trace = lambda planes, spans, anchor: {{
    "window_s": 1.0, "busy_s": 0.0, "busiest_busy_s": 0.0, "chips": 1,
    "programs": {{}}, "collective_s": 0.0, "harvests": 0,
    "device_ops": [], "idle_gaps": []}}
sys.exit(harness.main(sys.argv[1:]))
"""


@pytest.fixture(scope="module", params=["exec-node.saturate",
                                        "exec-node.paced"])
def traced_rehearsal(request):
    out = subprocess.run(
        [sys.executable, "-c",
         REHEARSAL.format(bench=str(BENCH), root=str(ROOT)),
         "--workload", request.param, "--seed", "3000000025", "--seconds",
         "8", "--trace", "1", "--platform", "cpu"],
        cwd=ROOT, env=ENV, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    return request.param.split(".")[1], line


def test_every_turn_metric_comes_out_as_a_rehearsal(traced_rehearsal):
    mode, line = traced_rehearsal
    values = {k: v["value"] for k, v in line["metrics"].items()}
    assert all(k.endswith(".cpu_rehearsal") for k in values)
    for name in NEW:
        key = (f"{name}.{mode}" if name in BY_MODE else name) + ".cpu_rehearsal"
        assert key in values, key
        other = "paced" if mode == "saturate" else "saturate"
        assert f"{name}.{other}.cpu_rehearsal" not in values
    get = lambda name: values[name + ".cpu_rehearsal"]   # noqa: E731
    assert line["correct"] is True
    # the program's own sum of turn walls against the harness's clock
    assert get("turn_host_ms_per_batch") * get("steps_per_s") == (
        pytest.approx(1000.0, rel=0.05))
    assert 95.0 <= get("turn_accounted_share") <= 100.0
    staged = sum(get(n) for n in (
        "pop_host_ms_per_batch", "source_wait_ms_per_batch",
        "chain_other_host_ms_per_batch", "fold_host_ms_per_batch",
        "update_dispatch_host_ms_per_batch", "slices_host_ms_per_batch",
        "window_planes_host_ms_per_batch"))
    assert 0.0 < staged < get("turn_host_ms_per_batch")
    assert get("window_compiles") == 0.0
    assert get(f"turn_max_ms.{mode}") >= get("turn_host_ms_per_batch")
    assert 0.0 < get("turn_max_cpu_share") <= 101.0


def test_a_program_without_the_accounting_reports_none():
    """The parent of the PR that added these metrics has no such counters
    and no `turn` key: the readers of the accounting find nothing and
    raise nothing."""
    import types
    sys.path[:0] = [str(BENCH), str(ROOT)]
    import run as harness
    from readers import turn

    def summary(ticks, stall_s):
        return types.SimpleNamespace(pipeline={
            "starved": 0, "saturated": ticks, "stall_s": stall_s})

    first, last = summary(2, 0.1), summary(6, 0.2)
    tap = types.SimpleNamespace(
        snap_start={"ig_tpusketch_steps_total": 1.0},
        snap_end={"ig_tpusketch_steps_total": 9.0},
        summaries=[(10.0, 0, first), (11.0, 5, last)],
        window_summaries=lambda: [(11.0, 5, last)],
        first_batch=1, window_start=10.0, counters_end=12.0)
    run = types.SimpleNamespace(tap=tap)
    cell, _config, traffic = harness.load_cell("exec-node.saturate", "tpu")
    mine = [m for m in harness.metrics_for(cell, traffic)
            if m["reader"].startswith("turn.")]
    assert len(mine) == len(NEW)
    # the stager's stall counters are older than the accounting
    assert harness.read(run, mine) == {
        "stager_stall_ms_per_batch": (25.0, "ms")}
    assert turn.count(run, "ig_tpusketch_steps_total") == 8.0


def test_benchmark_json_is_the_generators_with_new_entries_last():
    """BENCHMARK.json's `per_layer` holds what make_benchmark_json.py
    writes from the files; entries a PR adds go to the end of the list."""
    sys.path[:0] = [str(BENCH), str(ROOT)]
    import run as harness
    listed = json.loads((ROOT / "BENCHMARK.json").read_text())["per_layer"]
    names = [m["name"] for m in listed]
    assert len(set(names)) == len(names)
    on_disk = {json.loads(p.read_text())["name"]
               for p in (BENCH / "metrics").glob("*.json")}
    cells = [harness.load_cell(p.stem, "tpu")
             for p in (BENCH / "workloads").glob("*.json")]
    read_somewhere = {m["name"] for cell, _c, t in cells
                      for m in harness.metrics_for(cell, t)}
    assert set(names) == on_disk & read_somewhere
    first_new = min(names.index(n) for n in names
                    if n.split(".")[0] in NEW)
    assert all(n.split(".")[0] in NEW for n in names[first_new:])
