"""`BENCHMARK.json` against the data files, for `dense-node` and its cell:
pytest chipbench/tests/test_dense_cell.py -q

As `test_seccomp_cell.py` does for the entries before them: the file keeps
the order its entries were accepted in, so these tests hold this PR's
entries to the places they were accepted at, counted from the front:
everything the file had before (4 configurations, 5 cells, 41 per-layer
metrics), in its order, then the new. What a later PR appends behind them
breaks nothing here.
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

DENSE, DENSE_CELL = "dense-node", "dense-node.saturate"
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
# the file as it was accepted before this PR, by name and in its order
CONFIGS_BEFORE_DENSE = ["exec-node", "tcp-flows", "exec-host4",
                        "seccomp-node"]
CELLS_BEFORE_DENSE = ["exec-node.saturate", "exec-node.paced",
                      "tcp-flows.saturate", "exec-host4.saturate",
                      "seccomp-node.saturate"]
PER_LAYER_BEFORE_DENSE = 41
# each copy reads what its original reads, under `when.config: dense-node`
COPIES = {"anomaly_device_ms", "anomaly_host_ms", "anomaly_step_roofline",
          "container_dists_host_ms_per_batch",
          "gadget_record_host_ms_per_batch"}
HISTORY = {"seal_finish_ms.dense", "slices_dropped_share.dense"}
DENSE_METRICS = {n + ".dense" for n in COPIES} | HISTORY
ANOMALY_LAYER = "anomaly (gadget record, container distributions, scorer)"
HISTORY_LAYER = ("history window (window planes, _accumulate_slices, "
                 "seal_window)")


def test_the_dense_cell_and_its_configuration_are_the_files_in_their_places():
    cell, config, traffic = harness.load_cell(DENSE_CELL, "tpu")
    configs = [c["name"] for c in BENCHMARK["configs"]]
    cells = [w["name"] for w in BENCHMARK["workloads"]]
    assert configs[:5] == CONFIGS_BEFORE_DENSE + [DENSE]
    assert cells[:6] == CELLS_BEFORE_DENSE + [DENSE_CELL]
    assert BENCHMARK["workloads"][5] == {
        "name": DENSE_CELL, "config": DENSE, "traffic": "saturate",
        "chips": 1, "why": cell["why"]}
    assert BENCHMARK["configs"][4] == {
        "name": DENSE, "source": config["source"],
        "file": f"chipbench/configs/{DENSE}.json",
        "reduced": config["reduced"], "why": config["why"]}
    assert (traffic["mode"], traffic["rate"]) == ("saturate", 4_000_000)
    assert cell["end_to_end"] == ["events_per_s", "setup_s"]
    assert cell["order"] == 6
    for text in (cell["why"], config["why"], config["source"]):
        assert 0 < len(text) <= 200 and "\n" not in text and "\t" not in text
    assert sum(w["chips"] == 4 for w in BENCHMARK["workloads"]) <= max(
        len(BENCHMARK["workloads"]) // 2, 1)


def test_the_deployment_is_seccomp_nodes_at_upstreams_cap():
    config = harness.load("configs", DENSE)
    node = harness.load("configs", "seccomp-node")
    assert config["gadget"] == node["gadget"] == ["advise", "seccomp-profile"]
    assert config["gadget_params"] == {
        "source": "synthetic", "batch-size": "65536", "containers": "1024",
        "vocab": "343040", "zipf": "1.2"}
    # a key is a (container, syscall) pair, as in seccomp-node
    assert int(config["gadget_params"]["vocab"]) == 1024 * 335
    assert "MaxContainersPerNode 1024" in config["source"]
    # seccomp-node's operator, letter for letter and in its order, then
    # the documented parameter a node of 1,024 containers sets
    assert config["operator"] == {**node["operator"],
                                  "history-max-slices": "4096"}
    assert list(config["operator"])[:len(node["operator"])] == list(
        node["operator"])
    assert int(config["operator"]["history-max-slices"]) >= 2 * 1024 + 1
    assert "history-max-slices" in config["departures"]
    assert config["chips"] == 1
    assert config["reduced"] == node["reduced"] == [
        "harvest-interval", "history-interval"]
    # no limit is loosened against seccomp-node's (which are exec-node's)
    for name, limit in node["limits"].items():
        if name != "_set_from":
            assert config["limits"][name] <= limit, name
    assert set(config["guarantees"]) == set(node["guarantees"]) | {"slices"}
    assert "slices_dropped 0" in config["guarantees"]["slices"]
    assert {"containers", "skew", "stream"} <= set(config["assumed"])


def test_the_dense_metrics_and_the_lists_are_what_the_files_give():
    cell, _config, traffic = harness.load_cell(DENSE_CELL, "tpu")
    read_here = {m["name"] for m in harness.metrics_for(cell, traffic)}
    assert DENSE_METRICS <= read_here
    # the originals name seccomp-node alone: none of them is read here
    assert not (COPIES & read_here)
    names = [m["name"] for m in BENCHMARK["per_layer"]]
    at = PER_LAYER_BEFORE_DENSE
    assert set(names[at:at + len(DENSE_METRICS)]) == DENSE_METRICS
    for m in BENCHMARK["per_layer"][:at + len(DENSE_METRICS)]:
        on_disk = harness.load("metrics", m["name"])
        assert m == {**{k: on_disk[k] for k in (
            "name", "unit", "better", "source", "layer", "moves")},
            **({"workloads": m["workloads"]} if "workloads" in m else {})}
        if "workloads" in m:
            assert (DENSE_CELL in m["workloads"]) == (m["name"] in read_here)
            if DENSE_CELL in m["workloads"] and m["name"] not in DENSE_METRICS:
                # appended to the list the accepted cells were on
                place = m["workloads"].index(DENSE_CELL)
                assert set(m["workloads"][:place]) <= set(CELLS_BEFORE_DENSE)
        else:
            # no list: read in every cell that reports what it moves
            assert m["name"] in read_here, m["name"]
    for name in DENSE_METRICS:
        m = BENCHMARK["per_layer"][names.index(name)]
        assert m["workloads"] == [DENSE_CELL]
        assert m["moves"] == "events_per_s"
        assert m["layer"] == (HISTORY_LAYER if name in HISTORY
                              else ANOMALY_LAYER)
        assert harness.load("metrics", name)["when"] == {"config": [DENSE]}
    for name in COPIES:
        # the reader and the arguments of the file without the suffix
        copy, original = (harness.load("metrics", name + ".dense"),
                          harness.load("metrics", name))
        assert {k: copy[k] for k in copy if k not in ("name", "when")} == {
            k: original[k] for k in original if k not in ("name", "when")}
    roofline = BENCHMARK["per_layer"][names.index(
        "anomaly_step_roofline.dense")]
    assert (roofline["unit"], roofline["better"]) == ("%", "higher")
    for e in BENCHMARK["end_to_end"]:
        assert (DENSE_CELL in e.get("workloads", [DENSE_CELL])) == (
            e["name"] in cell["end_to_end"]), e["name"]


def dense_run_of(snap_end: dict, programs: dict | None, pipeline: dict):
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


def test_the_parents_program_reads_none_of_the_dense_metrics_it_lacks():
    """The parent of this PR cannot run the configuration at all (no
    `containers` parameter); a program that could, without this PR's
    counter and the seal worker's histogram, gives the new readers nothing
    to read and they raise nothing, traced or not."""
    mine = [harness.load("metrics", n) for n in sorted(HISTORY)]
    seconds = 'ig_pipeline_turn_seconds_total{stage="source_filter"}'
    for programs in (None, {"jit_bundle_ingest_step": [0.5, 100]}):
        run = dense_run_of({seconds: 1.0}, programs, {"turn": {}})
        assert harness.read(run, mine) == {}


def test_with_the_names_the_dense_metrics_come_out():
    mine = [harness.load("metrics", n) for n in sorted(DENSE_METRICS)]
    stage = 'ig_pipeline_turn_seconds_total{{stage="{}"}}'.format
    slices = 'ig_history_slices_total{{gadget="g",decision="{}"}}'.format
    finish = "ig_tpusketch_seal_finish_seconds"
    run = dense_run_of(
        {stage("gadget_record"): 0.004,
         stage("tpusketch_container_dists"): 0.008,
         stage("anomaly_score"): 0.006,
         slices("admitted"): 2049.0 * 3, slices("dropped"): 2049.0,
         finish + '_sum{gadget="g"}': 0.3, finish + '_count{gadget="g"}': 3.0},
        {"jit_anomaly_step": [0.004, 16]},
        {"anomaly": {"steps": 40, "containers": 1024, "slots": 1024}})
    got = harness.read(run, mine)
    assert got["gadget_record_host_ms_per_batch.dense"] == (0.5, "ms")
    assert got["container_dists_host_ms_per_batch.dense"] == (1.0, "ms")
    assert got["anomaly_host_ms.dense"] == (3.0, "ms")
    assert got["anomaly_device_ms.dense"] == (0.25, "ms")
    assert got["seal_finish_ms.dense"][0] == 100.0
    assert got["slices_dropped_share.dense"] == (25.0, "%")
    # 1,024 rows of 4096-256-64: operations and bytes meet
    from readers import scorer
    work = scorer.step_work(1024, [4096, 256, 64])
    least = max(work["bytes"] / 819e9, work["ops"] / 197e12)
    assert abs(work["bytes"] / 819e9 - work["ops"] / 197e12) < 0.1 * least
    share, unit = got["anomaly_step_roofline.dense"]
    assert unit == "%"
    assert abs(share - 100.0 * least / 0.25e-3) < 1e-9
    assert 0.0 < share < 100.0
