"""testjson2md — JSON test/bench records → markdown report.

Analogue of the reference's tools/testjson2md (converts `go test -json`
streams into a markdown summary for CI). Input: JSON lines on stdin or a
file. Two record shapes are understood:

- go-test-json style: {"Action": "pass|fail|skip", "Test": "...",
  "Elapsed": 1.2} (non-terminal actions are ignored)
- generic / bench:    {"name"|"metric": ..., "outcome"|"value": ...,
  "duration"|"unit": ..., "vs_baseline": ...}

Usage: python -m tools.testjson2md [file.jsonl ...] > report.md
"""

from __future__ import annotations

import json
import sys
from typing import Iterable, TextIO

_ICON = {"pass": "✅", "fail": "❌", "skip": "⏭️"}


def _parse(lines: Iterable[str]) -> tuple[list[dict], list[dict]]:
    tests, benches = [], []
    for line in lines:
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if not isinstance(rec, dict):
            continue
        if "Action" in rec:  # go test -json shape
            if rec.get("Action") in _ICON and rec.get("Test"):
                tests.append({"name": rec["Test"],
                              "outcome": rec["Action"],
                              "duration": rec.get("Elapsed", 0.0)})
        elif "metric" in rec:  # benchmark result shape
            benches.append(rec)
        elif "name" in rec and "outcome" in rec:
            tests.append({"name": rec["name"], "outcome": rec["outcome"],
                          "duration": rec.get("duration", 0.0)})
    return tests, benches


def render(tests: list[dict], benches: list[dict]) -> str:
    out = ["# Test report", ""]
    if tests:
        npass = sum(t["outcome"] == "pass" for t in tests)
        nfail = sum(t["outcome"] == "fail" for t in tests)
        nskip = sum(t["outcome"] == "skip" for t in tests)
        out += [f"**{len(tests)} tests** — {npass} passed, {nfail} failed, "
                f"{nskip} skipped", "",
                "| Test | Outcome | Duration |", "|---|---|---|"]
        for t in sorted(tests, key=lambda t: (t["outcome"] != "fail",
                                              t["name"])):
            icon = _ICON.get(t["outcome"], t["outcome"])
            out.append(f"| `{t['name']}` | {icon} {t['outcome']} "
                       f"| {t['duration']:.2f}s |")
        out.append("")
    if benches:
        out += ["## Benchmarks", "",
                "| Metric | Value | Unit | vs baseline |", "|---|---|---|---|"]
        for b in benches:
            vsb = b.get("vs_baseline")
            vs = f"{vsb:.2f}×" if isinstance(vsb, (int, float)) else "—"
            out.append(f"| {b['metric']} | {b.get('value'):,} "
                       f"| {b.get('unit', '')} | {vs} |")
        out.append("")
    if not tests and not benches:
        out.append("_no records found_")
    return "\n".join(out)


def main(argv: list[str], stdin: TextIO = sys.stdin) -> int:
    lines: list[str] = []
    if argv:
        for path in argv:
            with open(path) as f:
                lines += f.readlines()
    else:
        lines = stdin.readlines()
    print(render(*_parse(lines)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
