"""Readings for the limits: sound runs, the control and the planted faults,
on the chip at the cell's own size, many short windows in one process.

    python3 chipbench/control.py --workload <cell> --seconds <s> \
        --seeds 1,2,3 [--faults shed,half,altered] [--rates 1e6,2e6] \
        [--set vocab=1000]

One JSON line per run: which fault (or none), the seed, `correct`, every
number compared beside its limit, the rate absorbed, the delivered rate and
the ring drops. `--rates` repeats the sound run at other nominal rates: the
sweep that finds a paced cell's rate. `--set` repeats it with a gadget
parameter changed (a one-off reading of another key space, never a cell). The
benchmark's own runs (run.py) never come here.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys

import run as harness
from faults import planted


def one(cell, config, traffic, seed, seconds, fault):
    with planted(fault) if fault else contextlib.nullcontext():
        tap, around = harness.measure(config, traffic, cell, seed, seconds,
                                      traced=False)
    correct, compared = harness.reference.compare(
        tap, seed=seed, geometry=harness.geometry_of(config),
        limits=config["limits"],
        seal_failures=around["seal_failures"])
    window_s = tap.window_end - tap.window_start
    absorbed = tap.absorbed(tap.first_batch, tap.last_batch)
    shed = tap.shed(tap.first_batch, tap.last_batch)
    lags = sorted(tap.lags_ms())
    attempted, failed, cadence = harness.operations(
        tap, config, traffic["mode"], around["seal_failures"])
    print(json.dumps({
        "fault": fault or "none", "seed": seed, "nominal": traffic["rate"],
        "correct": correct, "events_per_s": absorbed / window_s,
        "delivered_per_s": (absorbed + shed) / window_s, "ring_drops": shed,
        "batches": tap.last_batch - tap.first_batch,
        "vocab": config["gadget_params"]["vocab"],
        "attempted": attempted, "failed": failed, **cadence,
        "lag_ms_p50_p95_max": ([float(x) for x in (
            lags[len(lags) // 2], lags[int(len(lags) * .95)], lags[-1])]
            if lags else None),
        "compared": {k: [v["value"], v["limit"]] for k, v in compared.items()},
    }), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(prog="chipbench/control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default="")
    ap.add_argument("--rates", default="")
    ap.add_argument("--set", default="", dest="override")
    ap.add_argument("--platform", default="tpu", choices=("tpu", "cpu"))
    args = ap.parse_args()
    cell, config, traffic = harness.load_cell(args.workload, args.platform)
    if not harness.acquire(cell, args.platform):
        return 1
    seeds = [int(s) for s in args.seeds.split(",")]
    harness.warm_up(config, traffic, seeds[0])
    for rate in [float(r) for r in args.rates.split(",") if r] or [None]:
        t = dict(traffic, rate=int(rate)) if rate else traffic
        for seed in seeds:
            one(cell, config, t, seed, args.seconds, None)
    for fault in [f for f in args.faults.split(",") if f]:
        for seed in seeds[:3]:
            one(cell, config, traffic, seed, args.seconds, fault)
    if args.override:
        key, value = args.override.split("=")
        other = dict(config, gadget_params={**config["gadget_params"],
                                            key: value})
        for seed in seeds[:3]:
            one(cell, other, traffic, seed, args.seconds, None)
    return 0


if __name__ == "__main__":
    sys.exit(main())
