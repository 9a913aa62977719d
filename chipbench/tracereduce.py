"""From a profiler trace (`.xplane.pb`) to the numbers the readers use.

    python3 chipbench/tracereduce.py <file.xplane.pb>     look at a trace by hand

The harness brackets the traced part of the window with two host
annotations of its own (`chipbench:trace_open`, `chipbench:trace_close`);
device time is clipped to the interval between them, so nothing needs the
host's and the profiler's clocks to agree. What the reduction reads:

  device planes  `/device:TPU:<n>`: line `XLA Ops` (one event per executed
                 operation; their union is the chip's busy time) and line
                 `XLA Modules` (one event per executed program, named after
                 the jitted function)
  host planes    every line: the program's own annotations
                 (`ig:tpusketch_update`, `ig:tpusketch_harvest`) name what
                 the host was doing during a device idle gap; the program's
                 tracer spans (`tpusketch/h2d`, `tpusketch/seal-window`),
                 handed in by the harness on the same clock, name the rest
"""

from __future__ import annotations

import bisect
import sys
from collections import defaultdict

OPEN, CLOSE = "chipbench:trace_open", "chipbench:trace_close"
HARVEST_ANNOTATION = "ig:tpusketch_harvest"
COLLECTIVES = ("all-reduce", "all-gather", "all_reduce", "all_gather")


def load(path: str) -> list[dict]:
    """Planes of an xplane file as plain data:
    [{"name", "lines": [{"name", "events": [(name, start_ns, dur_ns)]}]}]."""
    from jax.profiler import ProfileData
    return planes_of(ProfileData.from_file(path))


def planes_of(data) -> list[dict]:
    out = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            ev = [(e.name, float(e.start_ns), float(e.duration_ns))
                  for e in line.events]
            lines.append({"name": line.name, "events": ev})
        out.append({"name": plane.name, "lines": lines})
    return out


def union_seconds(intervals: list[tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total / 1e9


def gaps(intervals: list[tuple[float, float]], lo: float,
         hi: float) -> list[tuple[float, float]]:
    out, end = [], lo
    for a, b in sorted(intervals):
        if a > end:
            out.append((end, min(a, hi)))
        end = max(end, b)
    if end < hi:
        out.append((end, hi))
    return out


def short_name(module: str, op: str) -> str:
    """`jit_f(123)` and `%fusion.2 = s32[...] fusion(...)` -> `jit_f/fusion.2`."""
    return (module.split("(")[0] + "/" + op.split(" = ")[0].lstrip("%"))[:96]


def _clip(events, lo, hi):
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            yield name, a, b


def reduce_trace(planes: list[dict], host_spans: list[tuple[str, float, float]]
                 | None = None, anchor_wall_ns: float | None = None) -> dict:
    """`host_spans` are (name, start, end) in wall-clock ns, of the
    program's tracer; `anchor_wall_ns` is the wall clock at which the
    harness emitted OPEN, which puts them on the trace's clock."""
    host = [ln for p in planes if not p["name"].startswith("/device:")
            for ln in p["lines"]]
    marks = {name: start for ln in host for name, start, _d in ln["events"]
             if name in (OPEN, CLOSE)}
    if OPEN not in marks or CLOSE not in marks:
        raise ValueError("trace lacks the harness's open/close annotations")
    lo, hi = marks[OPEN], marks[CLOSE]
    annotations = [(n, a, b) for ln in host
                   for n, a, b in _clip(ln["events"], lo, hi)
                   if n.startswith("ig:")]
    if host_spans and anchor_wall_ns is not None:
        shift = lo - anchor_wall_ns
        annotations += list(_clip(
            [(n, a + shift, b - a) for n, a, b in host_spans], lo, hi))

    chips = []
    ops_time: dict[str, float] = defaultdict(float)
    for p in planes:
        if not p["name"].startswith("/device:TPU:"):
            continue
        ops, modules = [], []
        for ln in p["lines"]:
            if ln["name"] == "XLA Ops":
                ops = list(_clip(ln["events"], lo, hi))
            elif ln["name"] == "XLA Modules":
                modules = sorted(_clip(ln["events"], lo, hi),
                                 key=lambda e: e[1])
        if not ops and not modules:
            continue
        busy = union_seconds([(a, b) for _n, a, b in (ops or modules)])
        starts = [a for _n, a, _b in modules]
        for n, a, b in ops:
            i = bisect.bisect_right(starts, a) - 1
            inside = i >= 0 and a < modules[i][2]
            ops_time[short_name(modules[i][0] if inside else "", n)] += (
                b - a) / 1e9
        programs: dict[str, list] = defaultdict(lambda: [0.0, 0])
        for n, a, b in modules:
            row = programs[n.split("(")[0]]
            row[0] += (b - a) / 1e9
            row[1] += 1
        chips.append({
            "plane": p["name"], "busy_s": busy, "programs": dict(programs),
            "collective_s": sum(b - a for n, a, b in ops
                                if any(c in n for c in COLLECTIVES)) / 1e9,
            "intervals": [(a, b) for _n, a, b in (ops or modules)],
        })
    if not chips:
        raise ValueError("no device plane with operations in the trace")
    busiest = max(chips, key=lambda c: c["busy_s"])
    idle = defaultdict(float)
    for a, b in gaps(busiest["intervals"], lo, hi):
        # a gap goes to the host span that covers most of it
        cover = defaultdict(float)
        for n, x, y in annotations:
            o = min(b, y) - max(a, x)
            if o > 0:
                cover[n] += o
        name = max(cover, key=cover.get) if cover else "none"
        if cover and cover[name] < 0.5 * (b - a):
            name = "pop/fold/other"
        idle[name] += (b - a) / 1e9
    n = len(chips)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(c["busy_s"] for c in chips) / n,
        "busiest_busy_s": busiest["busy_s"],
        "chips": n,
        # {program: [device seconds, runs]} on the busiest chip (under
        # shard-ingest every chip runs each program once a round)
        "programs": busiest["programs"],
        "collective_s": busiest["collective_s"],
        "harvests": sum(1 for nm, _a, _b in annotations
                        if nm == HARVEST_ANNOTATION),
        "device_ops": sorted(ops_time.items(), key=lambda kv: -kv[1])[:10],
        "idle_gaps": sorted(idle.items(), key=lambda kv: -kv[1])[:10],
    }


def main(argv: list[str]) -> int:
    planes = load(argv[1])
    for p in planes:
        print(f"plane {p['name']!r}: {len(p['lines'])} lines")
        for ln in p["lines"]:
            ev = ln["events"]
            names = defaultdict(lambda: [0, 0.0])
            for n, _s, d in ev:
                names[n][0] += 1
                names[n][1] += d
            top = sorted(names.items(), key=lambda kv: -kv[1][1])[:12]
            span = (min(s for _n, s, _d in ev), max(s + d for _n, s, d in ev)
                    ) if ev else (0, 0)
            print(f"  line {ln['name']!r}: {len(ev)} events, "
                  f"{span[0]:.0f}..{span[1]:.0f} ns")
            for n, (c, d) in top:
                print(f"      {c:6d} x {d / 1e6:10.3f} ms  {n[:100]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
