"""chipbench: one cell, one window, one result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Drives `LocalRuntime.run_gadget` exactly as a user's `ig-tpu trace <gadget>
--source synthetic --tpusketch-enable true` does: native source -> pop ->
operator chain -> tpusketch (fold, stage, update step on the device, harvest,
seal). Everything a cell is made of is data found by name: the cell in
`workloads/`, its deployment in `configs/`, its traffic in `traffic/`, each
metric in `end_to_end/` or `metrics/` with its reader in `readers/`. See
README.md.

Without a TPU (or with fewer chips than the cell asks for) it exits non-zero
and prints no result. `--platform cpu` is the rehearsal the tests run: tiny
sizes from `rehearsal.json`, every metric name suffixed `.cpu_rehearsal`.
"""

from __future__ import annotations

import time

PROCESS_START = time.time()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [p for p in (str(HERE), str(HERE.parent)) if p not in sys.path]

import reference  # noqa: E402
import tracereduce as trace_reduction  # noqa: E402
from readers.registry import total  # noqa: E402
from tap import Tap  # noqa: E402

TRACE_DIR = HERE / "traces"          # git-ignored; emptied before each trace
TRACE_LAST_S = 4.0                   # the traced tail of a --trace 1 window
GEOMETRY_KEYS = ("depth", "log2-width", "hll-p", "entropy-log2-width", "topk")


def load(kind: str, name: str) -> dict:
    path = HERE / kind / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"chipbench: no file {path} for {name!r}")
    return json.loads(path.read_text())


def load_cell(workload: str, platform: str) -> tuple[dict, dict, dict]:
    """The cell, its configuration and its traffic, by name; at the
    rehearsal's sizes on the CPU."""
    cell = load("workloads", workload)
    config = load("configs", cell["config"])
    traffic = load("traffic", cell["traffic"])
    if platform == "cpu":
        tiny = json.loads((HERE / "rehearsal.json").read_text())
        config["gadget_params"].update(tiny["gadget_params"])
        config["operator"].update(tiny["operator"])
        traffic["rate"] = tiny["rate"]
    return cell, config, traffic


def acquire(cell: dict, platform: str) -> str | None:
    """The device or nothing: the compile cache's directory, or None (said
    on stderr) without the platform or with fewer chips than the cell asks."""
    import jax
    from inspektor_gadget_tpu.sources.bridge import NativeCapture
    from inspektor_gadget_tpu.utils.compile_cache import ensure_compile_cache
    from inspektor_gadget_tpu.utils.platform_probe import (
        PlatformUnavailable, acquire_platform)

    if platform == "cpu" and cell["chips"] > 1:
        jax.config.update("jax_num_cpu_devices", cell["chips"])
    cache_dir = ensure_compile_cache()
    # the small programs (digest, window advance) too: nothing recompiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    try:
        acq = acquire_platform(platform)
    except PlatformUnavailable as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return None
    if acq["device_count"] < cell["chips"]:
        print(f"chipbench: {cell['name']} needs {cell['chips']} chip(s), "
              f"JAX reports {acq['device_count']}", file=sys.stderr)
        return None
    NativeCapture(1).close()        # builds the native library (make decides)
    return cache_dir


def metrics_for(cell: dict, traffic: dict) -> list[dict]:
    """Every per-layer metric whose `when` matches this cell."""
    facts = {"mode": traffic["mode"], "chips": cell["chips"],
             "config": cell["config"], "cell": cell["name"]}
    out = []
    for path in sorted((HERE / "metrics").glob("*.json")):
        m = json.loads(path.read_text())
        if all(facts.get(k) in allowed for k, allowed in m["when"].items()):
            out.append(m)
    return out


def geometry_of(config: dict) -> dict:
    return {k: int(config["operator"][k]) for k in GEOMETRY_KEYS}


class Run:
    """What the readers are given."""

    def __init__(self, tap: Tap, config: dict, device_kind: str,
                 setup_s: float):
        op = config["operator"]
        self.tap = tap
        self.setup_s = setup_s
        self.geometry = geometry_of(config)
        self.batch_size = int(config["gadget_params"]["batch-size"])
        # key columns that differ stage one lane each, plus the weight lane
        self.staged_lanes = 1 + len({op.get("hh-column", "key_hash"),
                                     op.get("distinct-column", "key_hash"),
                                     op.get("dist-column", "key_hash")})
        self.device_kind = device_kind
        self.trace: dict | None = None


def gadget_context(config: dict, traffic: dict, seed: int, history_dir: str,
                   hooks: dict):
    import inspektor_gadget_tpu.all_gadgets  # noqa: F401
    from inspektor_gadget_tpu.gadgets import GadgetContext, get
    from inspektor_gadget_tpu.operators.operators import get as get_op
    from inspektor_gadget_tpu.params import Collection

    desc = get(*config["gadget"])
    params = desc.params().to_params()
    for k, v in {**config["gadget_params"], "rate": str(traffic["rate"]),
                 "seed": str(seed)}.items():
        params.set(k, str(v))
    tp = get_op("tpusketch").instance_params().to_params()
    for k, v in {**config["operator"], "history-dir": history_dir}.items():
        tp.set(k, str(v))
    ops = Collection()
    ops["operator.tpusketch."] = tp
    return GadgetContext(desc, gadget_params=params, operator_params=ops,
                         timeout=900.0, extra=hooks)


def run_gadget(ctx, on_batch):
    from inspektor_gadget_tpu.runtime import LocalRuntime
    result = LocalRuntime().run_gadget(ctx, on_batch=on_batch)
    if result.errors():
        raise SystemExit(f"chipbench: gadget run failed: {result.errors()}")


def warm_up(config: dict, traffic: dict, seed: int) -> None:
    """One short run at the cell's own shapes: compiles (or reads from the
    cache) the update step, the window planes, the digest and the seal."""
    seen = {"summaries": 0, "sealed": 0}

    def note(kind: str) -> None:
        seen[kind] += 1
        if seen["summaries"] >= 2 and seen["sealed"] >= 1:
            ctx.cancel()

    with tempfile.TemporaryDirectory(prefix="chipbench-warm-") as d:
        ctx = gadget_context(config, traffic, seed, d, {
            "on_sketch_summary": lambda _s: note("summaries"),
            "on_window_sealed": lambda _w: note("sealed")})
        try:
            run_gadget(ctx, None)
        finally:
            close_history()


def close_history() -> None:
    from inspektor_gadget_tpu.history import HISTORY
    HISTORY.close_all()


def measure(config: dict, traffic: dict, cell: dict, seed: int,
            seconds: float, traced: bool) -> tuple[Tap, dict]:
    """The measured run. Returns the tap and what was read around it."""
    import jax
    from inspektor_gadget_tpu.telemetry import snapshot
    from inspektor_gadget_tpu.telemetry.tracing import TRACER

    anchor: dict = {}

    def start_trace() -> None:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        anchor["wall_ns"] = time.time_ns()
        with jax.profiler.TraceAnnotation(trace_reduction.OPEN):
            pass

    def cancel() -> None:
        if anchor:
            with jax.profiler.TraceAnnotation(trace_reduction.CLOSE):
                pass
        ctx.cancel()

    # room for the source's nominal rate over the whole run: the loop cannot
    # absorb more than is offered, and pages never written cost nothing
    per_s = int(traffic["rate"])
    batch = int(config["gadget_params"]["batch-size"])
    tap = Tap(seconds=seconds, capacity_events=int(per_s * (seconds + 5)),
              capacity_batches=int(64 * per_s * (seconds + 5) / batch) + 4096,
              cancel=cancel, snapshot=snapshot,
              trace_last_s=min(TRACE_LAST_S, seconds / 2),
              start_trace=start_trace if traced else None)
    seal_failures0 = total(snapshot(), "ig_history_drops_total")
    history_dir = tempfile.mkdtemp(prefix="chipbench-hist-")
    ctx = gadget_context(config, traffic, seed, history_dir, {
        "on_sketch_summary": tap.on_summary,
        "on_window_sealed": tap.on_sealed})
    try:
        run_gadget(ctx, tap.on_batch)
    finally:
        if anchor:
            jax.profiler.stop_trace()
        close_history()
        shutil.rmtree(history_dir, ignore_errors=True)
    if tap.overflow or tap.window_end is None:
        raise SystemExit(f"chipbench: the run did not close its window: "
                         f"{tap.overflow or 'ended early'}")
    around = {
        "seal_failures": (total(snapshot(), "ig_history_drops_total")
                          - seal_failures0),
        "anchor_wall_ns": anchor.get("wall_ns"),
        "host_spans": [(r.name, r.start * 1e9, (r.start + r.duration) * 1e9)
                       for r in TRACER.records()
                       if r.name in ("tpusketch/h2d", "tpusketch/seal-window")
                       ] if traced else [],
    }
    return tap, around


def operations(tap: Tap, config: dict, mode: str, seal_failures: float
               ) -> tuple[int, int, dict]:
    """attempted, failed, and the cadence they were judged by. An operation
    is a summary due or a window due to seal. The program harvests (seals)
    at the first batch boundary after its interval has passed, so a sound
    run emits one every interval plus a turn of the loop, and up to a second
    interval later where a seal falls into the same turn (README.md has the
    readings). A gap may therefore hold two whole intervals; each one more
    is one summary (seal) missing. A seal also fails where the history store
    dropped it; in a paced cell a summary also fails where the ring shed
    events during its interval."""
    from inspektor_gadget_tpu.params.params import parse_duration
    inside = tap.window_summaries()

    def gaps(times: list[float]) -> list[float]:
        edges = [tap.window_start] + times + [tap.window_end]
        return [b - a for a, b in zip(edges, edges[1:])]

    def missing(times: list[float], interval: float) -> int:
        return sum(max(int(g // interval) - 2, 0) for g in gaps(times))

    summaries = [t for t, _b, _s in inside]
    seals = [t for t, _h in tap.sealed
             if tap.window_start <= t <= tap.window_end]
    lost = (missing(summaries,
                    parse_duration(config["operator"]["harvest-interval"]))
            + missing(seals,
                      parse_duration(config["operator"]["history-interval"])))
    shed = 0
    if mode == "paced":
        marks = [tap.first_batch - 1] + [b for _t, b, _s in inside]
        shed = sum(1 for a, b in zip(marks, marks[1:])
                   if tap.drops[min(b, tap.batches - 1)] > tap.drops[a])
    cadence = {"summary_gap_max_s": max(gaps(summaries)),
               "seal_gap_max_s": max(gaps(seals))}
    return (len(inside) + len(seals) + lost,
            lost + int(seal_failures) + shed, cadence)


def read(run: Run, metrics: list[dict]) -> dict:
    """Each metric through the reader its file names; one that finds
    nothing to read is left out."""
    out = {}
    for m in metrics:
        module, func = m["reader"].split(".")
        reader = getattr(importlib.import_module(f"readers.{module}"), func)
        value = reader(run, **m["args"])
        if value is not None:
            out[m["name"]] = (float(value), m["unit"])
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--platform", default="tpu", choices=("tpu", "cpu"))
    ap.add_argument("--keep-trace", default="",
                    help="copy the .xplane.pb here before it is deleted")
    args = ap.parse_args(argv)

    cell, config, traffic = load_cell(args.workload, args.platform)
    suffix = ".cpu_rehearsal" if args.platform == "cpu" else ""
    cache_dir = acquire(cell, args.platform)
    if not cache_dir:
        return 1
    import jax
    warm_up(config, traffic, args.seed)
    tap, around = measure(config, traffic, cell, args.seed, args.seconds,
                          bool(args.trace))
    setup_s = tap.window_start - PROCESS_START

    devices = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices[:cell["chips"]]]
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(max(peaks))}

    run = Run(tap, config, devices[0].device_kind, setup_s)
    result: dict = {}
    if args.trace:
        files = sorted(TRACE_DIR.glob("plugins/profile/*/*.xplane.pb"))
        if not files:
            raise SystemExit("chipbench: the profiler wrote no trace")
        if args.keep_trace:
            shutil.copy(files[-1], args.keep_trace)
        run.trace = trace_reduction.reduce_trace(
            trace_reduction.load(str(files[-1])), around["host_spans"],
            around["anchor_wall_ns"])
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        values = read(run, metrics_for(cell, traffic))
    else:
        values = read(run, [load("end_to_end", n) for n in cell["end_to_end"]])

    # the reference runs last: window closed, peak read, program state gone
    t_ref = time.time()
    correct, compared = reference.compare(
        tap, seed=args.seed, geometry=run.geometry, limits=config["limits"],
        seal_failures=around["seal_failures"])
    attempted, failed, cadence = operations(tap, config, traffic["mode"],
                                            around["seal_failures"])
    window_s = tap.window_end - tap.window_start
    facts = {
        "cell": cell["name"], "seed": args.seed, "window_s": window_s,
        "setup_s": setup_s, "compile_cache": cache_dir,
        "nominal_rate": traffic["rate"],
        "delivered_events_per_s": (
            tap.absorbed(tap.first_batch, tap.last_batch)
            + tap.shed(tap.first_batch, tap.last_batch)) / window_s,
        "batches": tap.last_batch - tap.first_batch,
        "summaries": len(tap.window_summaries()),
        "sealed": len(tap.sealed), **cadence,
        "reference_s": time.time() - t_ref,
        "total_s": time.time() - PROCESS_START,
    }
    print(json.dumps({"facts": facts}), flush=True)
    for name, row in compared.items():
        print(f"compared {name} = {row['value']!r} limit {row['limit']!r}",
              file=sys.stderr)
    print(f"correct = {correct}", file=sys.stderr, flush=True)
    result = {
        "correct": bool(correct), "attempted": attempted, "failed": failed,
        "metrics": {k + suffix: {"value": v, "unit": u}
                    for k, (v, u) in values.items()},
        "device": device, **result, "compared": compared}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
