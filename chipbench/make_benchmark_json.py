"""Write BENCHMARK.json from the files under chipbench/ (run by the PR that
adds a cell, a configuration or a metric). Every entry comes from its file,
so the two cannot drift, and nothing here names a cell or a metric: cells are
`workloads/*.json` in the order of their `order` key (the order they were
proven in), end-to-end metrics `end_to_end/*.json`, per-layer metrics
`metrics/*.json`.

    python3 chipbench/make_benchmark_json.py
"""

from __future__ import annotations

import json
from pathlib import Path

import run as harness

HERE = Path(__file__).resolve().parent
RUN_SECONDS = 51      # fixed by the PR that defined the benchmark (PR 23)


def files(kind: str) -> list[dict]:
    return [json.loads(p.read_text()) for p in sorted((HERE / kind).glob("*.json"))]


def main() -> None:
    names = [c["name"] for c in sorted(
        files("workloads"), key=lambda c: (c.get("order", 1 << 30), c["name"]))]
    cells = {n: harness.load_cell(n, "tpu") for n in names}
    configs = {c["name"]: c for _cell, c, _t in cells.values()}
    per_layer = []
    for m in files("metrics"):
        reports = [n for n, (cell, _c, t) in cells.items()
                   if any(x["name"] == m["name"]
                          for x in harness.metrics_for(cell, t))]
        if not reports:
            continue
        entry = {k: m[k] for k in ("name", "unit", "better", "source",
                                   "layer", "moves")}
        movers = [n for n, (cell, _c, _t) in cells.items()
                  if m["moves"] in cell["end_to_end"]]
        if m["when"] or reports != movers:
            entry["workloads"] = reports
        per_layer.append(entry)
    end_to_end = []
    for e in files("end_to_end"):
        reports = [n for n, (cell, _c, _t) in cells.items()
                   if e["name"] in cell["end_to_end"]]
        if not reports:
            continue
        entry = {k: e[k] for k in ("name", "unit", "better", "bound", "source")}
        end_to_end.append(dict(entry, **({"workloads": reports}
                                         if len(reports) < len(cells) else {})))
    doc = {
        "command": ["python3", "chipbench/run.py"],
        "paths": ["chipbench"],
        "run_seconds": RUN_SECONDS,
        "configs": [{"name": n, "source": c["source"],
                     "file": f"chipbench/configs/{n}.json",
                     "reduced": c["reduced"], "why": c["why"]}
                    for n, c in configs.items()],
        "workloads": [{"name": n, "config": cell["config"],
                       "traffic": cell["traffic"], "chips": cell["chips"],
                       "why": cell["why"]} for n, (cell, _c, _t) in cells.items()],
        "end_to_end": end_to_end,
        "per_layer": per_layer,
    }
    for entry in doc["configs"] + doc["workloads"]:
        for key in ("why", "source"):
            assert len(entry.get(key, "")) <= 200, (entry["name"], key)
    (HERE.parent / "BENCHMARK.json").write_text(json.dumps(doc, indent=1) + "\n")


if __name__ == "__main__":
    main()
