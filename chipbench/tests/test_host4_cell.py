"""`BENCHMARK.json` against the data files, for the four-chip cell:
pytest chipbench/tests/test_host4_cell.py -q

`BENCHMARK.json` keeps the order its entries were accepted in, which
`make_benchmark_json.py` (sorted by file name) does not reproduce, so the
file is ordered by hand. These tests hold its content to what the files
under chipbench/ give, entry by entry, so that it cannot drift from them.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [p for p in (str(BENCH), str(ROOT)) if p not in sys.path]

import run as harness  # noqa: E402

CELL = "exec-host4.saturate"
DOC = json.loads((ROOT / "BENCHMARK.json").read_text())
# what sharding adds, and the merge's collectives: read on four chips only
FOUR_CHIPS_ONLY = {"harvest_collective_ms", "shard_lane_skew",
                   "shard_merge_host_ms", "shard_restage_host_ms_per_batch",
                   "shard_round_fill_share"}


def entry(kind: str, name: str) -> dict:
    return next(e for e in DOC[kind] if e["name"] == name)


def test_the_cell_and_its_configuration_are_the_files():
    cell, config, traffic = harness.load_cell(CELL, "tpu")
    assert entry("workloads", CELL) == {
        "name": CELL, "config": cell["config"], "traffic": cell["traffic"],
        "chips": cell["chips"], "why": cell["why"]}
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "exec-host4", "saturate-host4", 4)
    assert entry("configs", "exec-host4") == {
        "name": "exec-host4", "source": config["source"],
        "file": "chipbench/configs/exec-host4.json",
        "reduced": config["reduced"], "why": config["why"]}
    assert traffic["mode"] == "saturate"
    assert DOC["workloads"][-1]["name"] == CELL
    assert DOC["configs"][-1]["name"] == "exec-host4"
    assert sum(w["chips"] == 4 for w in DOC["workloads"]) <= max(
        len(DOC["workloads"]) // 2, 1)


def test_the_deployment_is_exec_node_but_for_the_layout():
    config = harness.load("configs", "exec-host4")
    node = harness.load("configs", "exec-node")
    assert config["gadget"] == node["gadget"]
    assert config["gadget_params"] == node["gadget_params"]
    layout = {"shard-ingest": "true", "chips": "4"}
    assert config["operator"] == {**node["operator"], **layout}
    assert config["chips"] == 4
    assert config["reduced"] == node["reduced"]
    assert config["departures"] == node["departures"]
    # no limit is loosened against exec-node's
    for name, limit in node["limits"].items():
        if name != "_set_from":
            assert config["limits"][name] <= limit, name
    assert set(config["guarantees"]) == set(node["guarantees"]) | {"merge"}


def test_the_metric_lists_are_what_the_files_give():
    cell, _config, traffic = harness.load_cell(CELL, "tpu")
    read_here = {m["name"]: m for m in harness.metrics_for(cell, traffic)}
    assert FOUR_CHIPS_ONLY <= set(read_here)
    listed = {m["name"]: m for m in DOC["per_layer"]}
    for name, m in listed.items():
        on_disk = harness.load("metrics", name)
        assert m == {**{k: on_disk[k] for k in (
            "name", "unit", "better", "source", "layer", "moves")},
            **({"workloads": m["workloads"]} if "workloads" in m else {})}
        if "workloads" in m:
            assert (CELL in m["workloads"]) == (name in read_here), name
        else:
            # no list: read in every cell that reports what it moves
            assert name in read_here, name
    for name in FOUR_CHIPS_ONLY:
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["moves"] in cell["end_to_end"]
    # this PR's entries close the list; a new cell is appended to a list
    names = [m["name"] for m in DOC["per_layer"]]
    assert set(names[-len(FOUR_CHIPS_ONLY):]) == FOUR_CHIPS_ONLY
    for m in DOC["per_layer"][:-len(FOUR_CHIPS_ONLY)]:
        assert CELL not in m.get("workloads", [])[:-1]
    for e in DOC["end_to_end"]:
        assert (CELL in e.get("workloads", [CELL])) == (
            e["name"] in cell["end_to_end"]), e["name"]


def test_a_program_without_the_names_reads_nothing():
    """The parent of the PR that added them has no such stage or counter:
    the readers of readers/shard.py find nothing and raise nothing."""
    import types

    from readers import shard
    tap = types.SimpleNamespace(
        snap_start={"ig_pipeline_turns_total": 1.0,
                    "ig_tpusketch_harvests_total": 1.0},
        snap_end={"ig_pipeline_turns_total": 9.0,
                  "ig_tpusketch_harvests_total": 3.0,
                  'ig_pipeline_turn_seconds_total{stage="source_pop"}': 1.0})
    run = types.SimpleNamespace(tap=tap)
    mine = [harness.load("metrics", n) for n in sorted(FOUR_CHIPS_ONLY)
            if n != "harvest_collective_ms"]
    assert all(m["reader"].startswith("shard.") for m in mine)
    assert harness.read(run, mine) == {}
    # and with them: fillers over lanes x rounds, busiest lane over the mean
    g = 'gadget="trace/exec"'
    tap.snap_end.update({
        f'{shard.ROUNDS}{{{g},kind="full"}}': 3.0,
        f'{shard.ROUNDS}{{{g},kind="flushed"}}': 2.0,
        f'{shard.FILLERS}{{{g}}}': 5.0,
        **{f'{shard.LANE_EVENTS}{{{g},lane="{k}"}}': v
           for k, v in enumerate((40.0, 40.0, 30.0, 10.0))},
        'ig_pipeline_turn_seconds_total{stage="tpusketch_shard_restage"}':
            0.016,
        'ig_pipeline_turn_seconds_total{stage="tpusketch_shard_merge"}':
            0.010})
    assert harness.read(run, mine) == {
        "shard_lane_skew": (40.0 * 4 / 120.0, "ratio"),
        "shard_merge_host_ms": (5.0, "ms"),
        "shard_restage_host_ms_per_batch": (2.0, "ms"),
        "shard_round_fill_share": (75.0, "%")}
