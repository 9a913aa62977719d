"""Cut a recorded `.xplane.pb` down to the text-proto fixture the test reads.

    python3 chipbench/tests/make_trace_fixture.py <trace.xplane.pb> <out.txt>

Keeps, from `chipbench:trace_open` on, `SLICE_NS` of: the device planes' `XLA
Ops` and `XLA Modules` lines and the host's `ig:` / `chipbench:` annotations,
and closes the slice with a `chipbench:trace_close` of its own. Times are
rebased to the slice's start; names are kept as recorded (cut to 120
characters).
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import tracereduce  # noqa: E402

SLICE_NS = 400e6


def main(src: str, dst: str) -> None:
    planes = tracereduce.load(src)
    lo = next(s for p in planes for ln in p["lines"]
              for n, s, _d in ln["events"] if n == tracereduce.OPEN)
    hi = lo + SLICE_NS
    out = []
    for pid, p in enumerate(planes, 1):
        device = p["name"].startswith("/device:TPU:")
        names: dict[str, int] = {}
        lines = []
        for lid, ln in enumerate(p["lines"], 1):
            if device and ln["name"] not in ("XLA Ops", "XLA Modules"):
                continue
            ev = [(n[:120], s, d) for n, s, d in ln["events"]
                  if lo <= s and s + d <= hi and (
                      device or n.startswith(("ig:", "chipbench:")))
                  and n != tracereduce.CLOSE]
            if not device and any(n == tracereduce.OPEN for n, _s, _d in ev):
                ev.append((tracereduce.CLOSE, hi, 1000.0))
            if not ev:
                continue
            rows = "".join(
                f"    events {{ metadata_id: {names.setdefault(n, len(names) + 1)}"
                f" offset_ps: {int((s - lo) * 1000)}"
                f" duration_ps: {int(d * 1000)} }}\n" for n, s, d in ev)
            lines.append(f"  lines {{ id: {lid} name: {ln['name']!r} "
                         f"timestamp_ns: 1000\n{rows}  }}\n".replace("'", '"'))
        if lines:
            meta = "".join(
                f"  event_metadata {{ key: {i} value {{ id: {i} name: "
                f"\"{n.replace(chr(92), chr(92) * 2).replace(chr(34), chr(92) + chr(34))}\" }} }}\n"
                for n, i in names.items())
            out.append(f"planes {{ id: {pid} name: \"{p['name']}\"\n"
                       + "".join(lines) + meta + "}\n")
    Path(dst).write_text("".join(out))


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2])
