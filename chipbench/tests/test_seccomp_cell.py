"""`BENCHMARK.json` against the data files, for `seccomp-node` and its cell:
pytest chipbench/tests/test_seccomp_cell.py -q

`BENCHMARK.json` keeps the order its entries were accepted in (what
`make_benchmark_json.py`, sorted by file name, does not reproduce), so the
file is ordered by hand and these tests hold it to the data, entry by
entry. They hold this PR's entries to the places they were accepted at,
counted from the front: everything the file had before (3 configurations,
4 cells, 36 per-layer metrics), in its order, then the new. What a later PR
appends behind them breaks nothing here.
"""

from __future__ import annotations

import json
import sys
import types
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [p for p in (str(BENCH), str(ROOT)) if p not in sys.path]

import run as harness  # noqa: E402

CONFIG, CELL = "seccomp-node", "seccomp-node.saturate"
DOC = json.loads((ROOT / "BENCHMARK.json").read_text())
# the file as it was accepted before this PR, by name and in its order
CONFIGS_BEFORE = ["exec-node", "tcp-flows", "exec-host4"]
CELLS_BEFORE = ["exec-node.saturate", "exec-node.paced",
                "tcp-flows.saturate", "exec-host4.saturate"]
PER_LAYER_BEFORE = 36
MINE = {"anomaly_device_ms", "anomaly_host_ms", "anomaly_step_roofline",
        "container_dists_host_ms_per_batch",
        "gadget_record_host_ms_per_batch"}
LAYER = "anomaly (gadget record, container distributions, scorer)"


def test_the_cell_and_its_configuration_are_the_files_in_their_places():
    cell, config, traffic = harness.load_cell(CELL, "tpu")
    configs = [c["name"] for c in DOC["configs"]]
    cells = [w["name"] for w in DOC["workloads"]]
    assert configs[:4] == CONFIGS_BEFORE + [CONFIG]
    assert cells[:5] == CELLS_BEFORE + [CELL]
    assert DOC["workloads"][4] == {
        "name": CELL, "config": CONFIG, "traffic": "saturate", "chips": 1,
        "why": cell["why"]}
    assert DOC["configs"][3] == {
        "name": CONFIG, "source": config["source"],
        "file": f"chipbench/configs/{CONFIG}.json",
        "reduced": config["reduced"], "why": config["why"]}
    assert (traffic["mode"], traffic["rate"]) == ("saturate", 4_000_000)
    assert cell["end_to_end"] == ["events_per_s", "setup_s"]
    assert cell["order"] == 5
    for text in (cell["why"], config["why"], config["source"]):
        assert 0 < len(text) <= 200 and "\n" not in text and "\t" not in text
    assert sum(w["chips"] == 4 for w in DOC["workloads"]) <= max(
        len(DOC["workloads"]) // 2, 1)


def test_the_deployment_is_exec_nodes_operator_with_the_scorer_on():
    config = harness.load("configs", CONFIG)
    node = harness.load("configs", "exec-node")
    assert config["gadget"] == ["advise", "seccomp-profile"]
    assert config["gadget_params"] == {
        "source": "synthetic", "batch-size": "65536", "vocab": "21440",
        "zipf": "1.2"}
    # a key is a (container, syscall) pair
    assert int(config["gadget_params"]["vocab"]) == 64 * 335
    assert config["operator"] == {**node["operator"], "anomaly": "true"}
    assert list(config["operator"])[:len(node["operator"])] == list(
        node["operator"])
    assert config["chips"] == 1
    assert config["reduced"] == node["reduced"] == [
        "harvest-interval", "history-interval"]
    assert config["departures"] == node["departures"]
    # no limit is loosened against exec-node's
    for name, limit in node["limits"].items():
        if name != "_set_from":
            assert config["limits"][name] <= limit, name
    assert set(config["guarantees"]) == set(node["guarantees"]) | {
        "anomaly", "profile"}
    for name in ("anomaly", "profile"):
        assert "reference_scorer.py" in config["guarantees"][name]


def test_the_five_metrics_and_the_lists_are_what_the_files_give():
    cell, _config, traffic = harness.load_cell(CELL, "tpu")
    read_here = {m["name"] for m in harness.metrics_for(cell, traffic)}
    assert MINE <= read_here
    names = [m["name"] for m in DOC["per_layer"]]
    # this PR's five follow what the file had, whatever follows them
    assert set(names[PER_LAYER_BEFORE:PER_LAYER_BEFORE + 5]) == MINE
    for m in DOC["per_layer"][:PER_LAYER_BEFORE + 5]:
        on_disk = harness.load("metrics", m["name"])
        assert m == {**{k: on_disk[k] for k in (
            "name", "unit", "better", "source", "layer", "moves")},
            **({"workloads": m["workloads"]} if "workloads" in m else {})}
        if "workloads" in m:
            assert (CELL in m["workloads"]) == (m["name"] in read_here)
            if CELL in m["workloads"] and m["name"] not in MINE:
                # appended to the list the accepted cells were on
                at = m["workloads"].index(CELL)
                assert set(m["workloads"][:at]) <= set(CELLS_BEFORE)
        else:
            # no list: read in every cell that reports what it moves
            assert m["name"] in read_here, m["name"]
    for name in MINE:
        m = DOC["per_layer"][names.index(name)]
        assert m["workloads"][0] == CELL
        assert m["layer"] == LAYER and m["moves"] == "events_per_s"
        assert harness.load("metrics", name)["when"] == {"config": [CONFIG]}
    roofline = DOC["per_layer"][names.index("anomaly_step_roofline")]
    assert (roofline["unit"], roofline["better"]) == ("%", "higher")
    for e in DOC["end_to_end"]:
        assert (CELL in e.get("workloads", [CELL])) == (
            e["name"] in cell["end_to_end"]), e["name"]


def run_of(snap_end: dict, programs: dict | None, pipeline: dict):
    """A measured run as the readers see it: registry snapshots, the
    reduced trace, and one summary inside the window."""
    summary = types.SimpleNamespace(pipeline=pipeline)
    tap = types.SimpleNamespace(
        snap_start={"ig_pipeline_turns_total": 1.0,
                    "ig_tpusketch_harvests_total": 1.0},
        snap_end={"ig_pipeline_turns_total": 9.0,
                  "ig_tpusketch_harvests_total": 3.0, **snap_end},
        window_summaries=lambda: [(0.0, 0, summary)])
    return types.SimpleNamespace(
        tap=tap, device_kind="TPU v5 lite",
        trace=None if programs is None else {"programs": programs})


def test_the_parents_program_reads_none_of_the_five():
    """The parent of this PR runs the configuration (eagerly) and has no
    such stage, counter, program or `pipeline` key: every one of the five
    readers finds nothing and raises nothing, traced or not."""
    mine = [harness.load("metrics", n) for n in sorted(MINE)]
    seconds = 'ig_pipeline_turn_seconds_total{stage="source_filter"}'
    for programs in (None, {"jit_bundle_ingest_step": [0.5, 100]}):
        run = run_of({seconds: 1.0}, programs, {"turn": {}})
        assert harness.read(run, mine) == {}


def test_with_the_names_the_five_come_out():
    from readers import scorer
    mine = [harness.load("metrics", n) for n in sorted(MINE)]
    stage = 'ig_pipeline_turn_seconds_total{{stage="{}"}}'.format
    run = run_of(
        {stage("gadget_record"): 0.004,
         stage("tpusketch_container_dists"): 0.008,
         stage("anomaly_score"): 0.006},
        {"jit_anomaly_step": [0.004, 16]},
        {"anomaly": {"steps": 40, "containers": 64, "slots": 64}})
    got = harness.read(run, mine)
    assert got["gadget_record_host_ms_per_batch"] == (0.5, "ms")
    assert got["container_dists_host_ms_per_batch"] == (1.0, "ms")
    assert got["anomaly_host_ms"] == (3.0, "ms")
    assert got["anomaly_device_ms"] == (0.25, "ms")
    # 64 rows of 4096-256-64: the parameters and Adam's moments bind
    work = scorer.step_work(64, [4096, 256, 64])
    params = 2 * (4096 * 256 + 256 * 64) + 256 + 64 + 256 + 4096
    assert work["bytes"] == 24 * params + 4 * 64 * 4096 + 4 * 64
    forward = 2 * 64 * 2 * (4096 * 256 + 256 * 64)
    assert work["ops"] == 4 * forward - 2 * 64 * 4096 * 256
    share, unit = got["anomaly_step_roofline"]
    assert unit == "%"
    assert abs(share - 100.0 * (work["bytes"] / 819e9) / 0.25e-3) < 1e-9
    assert 0.0 < share < 100.0
