"""`BENCHMARK.json` against the data files, for `exec-eventtime` and its
cell, and the cell's CPU rehearsal: pytest chipbench/tests/test_eventtime_cell.py -q

As `test_seccomp_cell.py` does for the entries before them: the file keeps
the order its entries were accepted in, so these tests hold this PR's
entries to the places they were accepted at, counted from the front:
everything the file had before (5 configurations, 6 cells, 53 per-layer
metrics), in its order, then the new. What a later PR appends behind them
breaks nothing here.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import types
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [p for p in (str(BENCH), str(ROOT)) if p not in sys.path]

import run as harness  # noqa: E402

CONFIG, CELL = "exec-eventtime", "exec-eventtime.saturate"
DOC = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS_BEFORE = ["exec-node", "tcp-flows", "exec-host4", "seccomp-node",
                  "dense-node"]
CELLS_BEFORE = ["exec-node.saturate", "exec-node.paced",
                "tcp-flows.saturate", "exec-host4.saturate",
                "seccomp-node.saturate", "dense-node.saturate"]
PER_LAYER_BEFORE = 53
MINE = ["window_route_host_ms_per_batch", "window_planes_device_ms",
        "window_planes_roofline", "window_seal_delay_ms",
        "late_events_share"]
LAYER = "history window (window planes, _accumulate_slices, seal_window)"
PROGRAM = "jit_window_planes_step"
# the loop metrics (chipbench/tests/test_loop_metrics.py), at their place
LOOP = ["loop_cpu_share", "loop_blocked_share", "gc_ms_per_s",
        "harvest_gap_max_ms", "summaries_failed"]
LOOP_AT = 48
LOOP_LAYER = "operator chain (runtime/local.py, enrich_batch host loop)"


def test_the_eventtime_cell_and_configuration_are_in_their_places():
    cell, config, traffic = harness.load_cell(CELL, "tpu")
    configs = [c["name"] for c in DOC["configs"]]
    cells = [w["name"] for w in DOC["workloads"]]
    assert configs[:6] == CONFIGS_BEFORE + [CONFIG]
    assert cells[:7] == CELLS_BEFORE + [CELL]
    assert DOC["workloads"][6] == {
        "name": CELL, "config": CONFIG, "traffic": "saturate", "chips": 1,
        "why": cell["why"]}
    assert DOC["configs"][5] == {
        "name": CONFIG, "source": config["source"],
        "file": f"chipbench/configs/{CONFIG}.json",
        "reduced": config["reduced"], "why": config["why"]}
    assert (traffic["mode"], traffic["rate"]) == ("saturate", 4_000_000)
    assert cell["end_to_end"] == ["events_per_s", "setup_s"]
    assert cell["order"] == 7
    for text in (cell["why"], config["why"], config["source"]):
        assert 0 < len(text) <= 200 and "\n" not in text and "\t" not in text
    assert sum(w["chips"] == 4 for w in DOC["workloads"]) == 1


def test_the_deployment_is_exec_nodes_stream_with_delays_and_event_time():
    config = harness.load("configs", CONFIG)
    node = harness.load("configs", "exec-node")
    assert config["gadget"] == node["gadget"] and config["chips"] == 1
    assert config["gadget_params"] == {
        **node["gadget_params"], "prob-delayed-event": "0.1",
        "occasional-delay": "3s"}
    assert config["operator"] == {
        **node["operator"], "history-interval": "10s",
        "history-lateness": "3s"}
    # the operator's documented default: only the harvest cadence is cut
    assert config["reduced"] == ["harvest-interval"]
    for name, limit in node["limits"].items():
        if name != "_set_from":
            assert config["limits"][name] <= limit, name
    assert set(config["guarantees"]) == set(node["guarantees"]) | {"windows"}
    assert "reference_windows.py" in config["guarantees"]["windows"]
    assert {"delay", "delay_model", "stream"} <= set(config["assumed"])


def test_the_window_metrics_and_the_lists_are_what_the_files_give():
    cell, _config, traffic = harness.load_cell(CELL, "tpu")
    read_here = {m["name"] for m in harness.metrics_for(cell, traffic)}
    assert set(MINE) <= read_here
    names = [m["name"] for m in DOC["per_layer"]]
    assert names[PER_LAYER_BEFORE:PER_LAYER_BEFORE + 5] == MINE
    for m in DOC["per_layer"][:PER_LAYER_BEFORE]:
        on_disk = harness.load("metrics", m["name"])
        assert m == {**{k: on_disk[k] for k in (
            "name", "unit", "better", "source", "layer", "moves")},
            **({"workloads": m["workloads"]} if "workloads" in m else {})}
        if "workloads" not in m:
            # no list: read in every cell that reports what it moves
            assert m["name"] in read_here, m["name"]
            continue
        # a list: the accepted cells it was read in, this cell appended
        # where it is read here too (later cells may follow it)
        assert (CELL in m["workloads"]) == (m["name"] in read_here)
        at = m["workloads"].index(CELL) if CELL in m["workloads"] else None
        assert set(m["workloads"][:at]) <= set(CELLS_BEFORE)
        assert at is None or not set(m["workloads"][at:]) & set(CELLS_BEFORE)
    for name in MINE:
        m = DOC["per_layer"][names.index(name)]
        on_disk = harness.load("metrics", name)
        assert m == {**{k: on_disk[k] for k in (
            "name", "unit", "better", "source", "layer", "moves")},
            "workloads": m["workloads"]}
        assert m["layer"] == LAYER and m["moves"] == "events_per_s"
        want = ([CELL] if name == "late_events_share"
                else CELLS_BEFORE + [CELL])
        assert m["workloads"][:len(want)] == want
    for e in DOC["end_to_end"]:
        assert (CELL in e.get("workloads", [CELL])) == (
            e["name"] in cell["end_to_end"]), e["name"]


def test_the_loop_metrics_are_the_six_cells_then_this_one():
    """The five loop metrics, read in every cell (no `when`): their
    lists are the six cells they were accepted with, this cell appended
    (later cells may follow), and each cell reads them."""
    names = [m["name"] for m in DOC["per_layer"]]
    assert names[LOOP_AT:LOOP_AT + len(LOOP)] == LOOP
    for m in DOC["per_layer"][LOOP_AT:LOOP_AT + len(LOOP)]:
        on_disk = harness.load("metrics", m["name"])
        assert on_disk["when"] == {}
        assert m == {**{k: on_disk[k] for k in (
            "name", "unit", "better", "source", "layer", "moves")},
            "workloads": m["workloads"]}
        assert m["workloads"][:len(CELLS_BEFORE) + 1] == CELLS_BEFORE + [CELL]
        assert (m["layer"], m["moves"]) == (LOOP_LAYER, "events_per_s")
    for cell in CELLS_BEFORE + [CELL]:
        read_here = {m["name"] for m in harness.metrics_for(
            *harness.load_cell(cell, "tpu")[::2])}
        assert set(LOOP) <= read_here, cell


def run_of(snap_start: dict, snap_end: dict, programs: dict | None,
           lanes: int = 2):
    tap = types.SimpleNamespace(
        snap_start={"ig_pipeline_turns_total": 1.0, **snap_start},
        snap_end={"ig_pipeline_turns_total": 11.0, **snap_end})
    return types.SimpleNamespace(
        tap=tap, device_kind="TPU v5 lite", staged_lanes=lanes,
        geometry={"depth": 4},
        trace=None if programs is None else {"programs": programs})


def test_the_parents_program_reads_none_of_the_window_metrics():
    """The parent of this PR has no such stage, counter, histogram or
    program: every reader finds nothing and raises nothing, traced or
    not."""
    mine = [harness.load("metrics", n) for n in MINE]
    seconds = 'ig_pipeline_turn_seconds_total{stage="tpusketch_fold"}'
    for programs in (None, {"jit_bundle_ingest_step": [0.5, 100],
                            "jit__wcms_ingest_step": [0.01, 100]}):
        run = run_of({seconds: 1.0}, {seconds: 2.0,
                                      "ig_tpusketch_steps_total": 10.0},
                     programs)
        assert harness.read(run, mine) == {}


def test_with_the_names_the_window_metrics_come_out():
    from readers import windows
    import work
    mine = [harness.load("metrics", n) for n in MINE]
    route = 'ig_pipeline_turn_seconds_total{stage="tpusketch_window_route"}'
    g = '{gadget="trace/exec"}'
    start = {route: 0.01, "ig_tpusketch_steps_total" + g: 100.0,
             "ig_tpusketch_step_rows_total" + g: 100.0 * 65536,
             "ig_tpusketch_events_total" + g: 5e6,
             "ig_history_late_events_total" + g: 0.0,
             "ig_history_seal_delay_seconds_sum" + g: 1.0,
             "ig_history_seal_delay_seconds_count" + g: 1.0}
    end = {route: 0.03, "ig_tpusketch_steps_total" + g: 110.0,
           "ig_tpusketch_step_rows_total" + g: 110.0 * 65536,
           "ig_tpusketch_events_total" + g: 6e6,
           "ig_history_late_events_total" + g: 10.0,
           "ig_history_seal_delay_seconds_sum" + g: 8.0,
           "ig_history_seal_delay_seconds_count" + g: 3.0}
    got = harness.read(run_of(start, end, {PROGRAM: [0.02, 100]}), mine)
    assert got["window_route_host_ms_per_batch"] == pytest.approx((2.0, "ms"))
    assert got["window_planes_device_ms"] == pytest.approx((0.2, "ms"))
    assert got["window_seal_delay_ms"] == pytest.approx((3500.0, "ms"))
    assert got["late_events_share"] == pytest.approx((1e-3, "%"))
    # two uint32 lanes and the age lane, six counters and the count a row
    w = windows.window_work(65536, 2, 1, 4)
    assert w["bytes"] == 65536 * (8 + 1 + 8 * 6 + 8)
    least, binds = work.least_seconds(w, work.peaks("TPU v5 lite"))
    assert binds == "bytes"
    share, unit = got["window_planes_roofline"]
    assert unit == "%" and share == pytest.approx(100 * least / 0.2e-3)
    assert 0.0 < share < 100.0
    # on the processing clock: no age lane, no late counter
    for k in list(start):
        if "late" in k:
            del start[k], end[k]
    got = harness.read(run_of(start, end, {PROGRAM: [0.02, 100]}), mine)
    assert "late_events_share" not in got
    least, _ = work.least_seconds(windows.window_work(65536, 2, 0, 4),
                                  work.peaks("TPU v5 lite"))
    assert got["window_planes_roofline"][0] == pytest.approx(
        100 * least / 0.2e-3)


# run.py's main() with the trace reduction stood in for (a CPU trace has no
# device plane to reduce), as chipbench/tests/test_turn_metrics.py does
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


def test_the_cells_cpu_rehearsal_is_correct_and_reads_its_metrics():
    """10 s windows behind a 3 s watermark: the warm-up waits for a seal
    (up to 13 s) and the 15 s window holds one or two more."""
    out = subprocess.run(
        [sys.executable, "-c",
         REHEARSAL.format(bench=str(BENCH), root=str(ROOT)),
         "--workload", CELL, "--seed", "3000000041", "--seconds", "15",
         "--trace", "1", "--platform", "cpu"],
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    facts = json.loads(out.stdout.strip().splitlines()[-2])["facts"]
    assert line["correct"] is True and line["failed"] == 0
    assert facts["sealed"] >= 2
    values = {k.removesuffix(".cpu_rehearsal"): v["value"]
              for k, v in line["metrics"].items()}
    assert values["late_events_share"] == 0.0
    assert values["window_route_host_ms_per_batch"] > 0.0
    # the lateness, and whatever the ring held behind it
    assert 3000.0 <= values["window_seal_delay_ms"] < 6000.0
