"""The benchmark's own tests, each as a tier-1 item.

`chipbench/` is the driver's yardstick (`BENCHMARK.json` `paths`) and its
tests live beside it, outside `tests/`; this file loads every
`chipbench/tests/test_*.py` and takes its tests and fixtures as its own, so a
change to the program that breaks the rehearsal, a reader or a planted fault
fails tier-1 and not only the next chip run.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

import pytest

_FIXTURE = type(pytest.fixture(lambda: None))

LEFT_OUT = {
    # holds PR 25's entries to the end of BENCHMARK.json's `per_layer`; PR
    # 27's follow them, as the driver's contract orders new entries, so it
    # fails by design (PERF.md section 7 (6)). test_host4_cell.py holds the
    # file to the data now; a `benchmark` issue rewrites or removes it.
    "test_benchmark_json_is_the_generators_with_new_entries_last",
    # holds `turn_accounted_share` of a CPU rehearsal to 95%, and a 3.5 ms
    # CPU turn reads 95.0-95.5% on an idle host at PR 28's tree as on this
    # one (94.97% once) and under that beside the suite: unsteady, both
    # cells. tests/test_turn_accounting.py holds the accounting itself (by
    # milliseconds a turn), tests/test_shard_host_rehearsal.py the
    # four-chip cell's names; a `benchmark` issue restates the bound.
    "test_every_turn_metric_comes_out_as_a_rehearsal",
    # chipbench/tests/test_host4_cell.py holds `exec-host4` to the LAST
    # place of `configs` and `workloads` and PR 27's five entries to the
    # END of `per_layer`; `seccomp-node`, its cell and its five metrics
    # follow them (ISSUE 31), as the contract orders new entries, so both
    # fail by design. chipbench/tests/test_seccomp_cell.py holds the file
    # to the data, every accepted entry included, by places counted from
    # the front; a `benchmark` issue restates the two (PERF.md section 7).
    "test_the_cell_and_its_configuration_are_the_files",
    "test_the_metric_lists_are_what_the_files_give",
    # chipbench/tests/test_loop_metrics.py holds the five loop metrics'
    # `workloads` to the six cells they were accepted with, while
    # test_benchmark_json_names_files_that_exist holds every list to the
    # cells that read it: every cell reads the loop metrics, so a seventh
    # (`exec-eventtime.saturate`) is appended to their lists and the
    # pinned six fail by design; test_eventtime_cell.py holds the lists to
    # the data, places counted from the front, and the metrics' files to
    # no `when` (PERF.md section 7)
    "test_the_six_are_appended_behind_the_accepted_entries",
}

for _path in sorted((Path(__file__).resolve().parents[1]
                     / "chipbench" / "tests").glob("test_*.py")):
    _spec = importlib.util.spec_from_file_location(
        f"chipbench_tests_{_path.stem}", _path)
    _mod = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(_mod)
    for _name, _obj in vars(_mod).items():
        if _name in LEFT_OUT:
            continue
        if (_name.startswith("test_") and callable(_obj)
                or isinstance(_obj, _FIXTURE)):
            assert _name not in globals(), f"{_path.name}: second {_name}"
            globals()[_name] = _obj
