"""Headline loop benchmark: sketch-ingest throughput (events/sec/chip).

BASELINE target: >=5M events/sec/node on trace exec + trace tcp streams
(BASELINE.md; the reference publishes no absolute throughput — its envelope
is bounded by per-event Go hot loops and 64-page perf rings).

One process, one device acquisition (utils/platform_probe): the process
that measures is the process that owns the chip. `--platform tpu` fails
when JAX reports no TPU; every phase that fails raises, so the exit code is
non-zero and no partial record is printed. The record names the platform,
device kind and count it ran on and the host generator that fed it, and
the metric carries the device name `sketch_ingest_throughput_e2e` only on a
TPU — any other backend's number is published as
`sketch_ingest_throughput_e2e_<platform>`, at the same shapes.

Method: a host producer thread runs the C++ synthetic source's FOLDED
exporter (zipf exec tuples, FNV-hashed keys xor-folded to uint32 in native
code — the ig_source_pop_folded contract) straight into pinned staging
blocks from a PinnedBufferPool; the consumer stages each block through the
depth-4 H2DStager (the transfer of batch k+1 overlaps device compute of
batch k) and runs the SketchBundle ingest step (count-min + HLL + entropy
+ top-k in one device step). Every event counted was generated, staged,
transferred, and sketched during the timed window. Steady-state,
first-compile excluded.

Secondary metrics ride the same JSON line under "extra":
  host_plane_ev_per_s    generator+fold throughput alone (no JAX at all)
  device_plane_ev_per_s  pre-staged device arrays, update loop only
  merge_ms_p50           single-chip bundle_merge latency
  gen_impl               "C++ SoA" | "py-fold": which host generator ran

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extra"}.
"""

from __future__ import annotations

import argparse
import json
import queue
import sys
import threading
import time

import numpy as np

BASELINE_EV_S = 5_000_000.0

# the production sketch shape, on every backend
SHAPE = dict(batch=1 << 17, log2_width=16, hll_p=14, entropy_log2_width=12,
             k=128, bench_seconds=3.0, device_seconds=1.5, merges=50)


def _make_gen(batch: int):
    """Host-side folded-key generator: the C++ synthetic source when the
    native library is available, else the numpy source — and the record
    says which (`impl`: "C++ SoA" | "py-fold"). No JAX involved either
    way. Returns (gen_into, impl): gen_into(out) fills a caller buffer (a
    pinned staging lane) in place."""
    from inspektor_gadget_tpu.sources.bridge import (
        SRC_SYNTH_EXEC, NativeCapture, native_available,
    )
    if native_available():
        src = NativeCapture(SRC_SYNTH_EXEC, seed=42, vocab=5000, zipf_s=1.2)
        return (lambda out: src.generate_folded(batch, out=out)), "C++ SoA"
    print("bench: native capture library unavailable; the numpy generator "
          "feeds the pipeline (gen_impl=py-fold)", file=sys.stderr)
    from inspektor_gadget_tpu.sources.synthetic import PySyntheticSource
    src = PySyntheticSource(seed=42, vocab=5000, batch_size=batch)

    def gen_into(out: np.ndarray) -> None:
        k = np.asarray(src.generate(batch).cols["key_hash"], dtype=np.uint64)
        out[:] = ((k >> np.uint64(32)) ^ (k & np.uint64(0xFFFFFFFF))).astype(
            np.uint32)

    return gen_into, "py-fold"


def host_plane_ev_per_s(gen_into, batch: int, seconds: float = 1.0) -> float:
    """Folded-exporter throughput with no JAX (pop_folded into a pinned
    pool block): the capture-path ceiling."""
    from inspektor_gadget_tpu.sources.staging import PinnedBufferPool
    pool = PinnedBufferPool(batch, lanes=1, max_free=2)
    block = pool.get()
    gen_into(block[0])  # warm (vocab tables, allocator)
    n = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        gen_into(block[0])
        n += batch
    return n / (time.perf_counter() - t0)


def run(chips: int = 1) -> dict:
    """The sketch pipeline on the already-acquired device. Any failed
    phase raises."""
    import jax
    import jax.numpy as jnp

    from inspektor_gadget_tpu.ops import bundle_merge
    from inspektor_gadget_tpu.ops.sketches import (
        bundle_ingest_jit, bundle_init,
    )
    from inspektor_gadget_tpu.sources.staging import (
        H2DStager, PinnedBufferPool,
    )

    cfg = SHAPE
    batch = cfg["batch"]
    gen_into, gen_impl = _make_gen(batch)
    host_ev_per_s = host_plane_ev_per_s(gen_into, batch)

    def new_bundle():
        return bundle_init(depth=4, log2_width=cfg["log2_width"],
                           hll_p=cfg["hll_p"],
                           entropy_log2_width=cfg["entropy_log2_width"],
                           k=cfg["k"])

    # the shared staged-ingest step (update + fence token — the
    # donation/fence contract is documented once, on
    # ops.sketches.bundle_ingest_step)
    def step(b, k, w):
        return bundle_ingest_jit(b, k, k, k, w)

    bundle = new_bundle()
    mask = jnp.ones(batch, dtype=jnp.int32)  # weights lane: every slot 1
    host_pool = PinnedBufferPool(batch, lanes=1, max_free=8)
    stager = H2DStager(host_pool, depth=4)

    for _ in range(3):  # compile + device warmup
        blk = host_pool.get()
        gen_into(blk[0])
        (k,) = stager.stage(blk, (blk[0],))
        bundle, tok = step(bundle, k, mask)
        stager.fence(tok)
    jax.block_until_ready(bundle.events)
    stager.drain()

    # ---- headline: end-to-end pipelined ingest ----------------------------
    # producer fills pinned pool blocks with the native folded exporter;
    # the consumer stages them through the depth-4 H2D ring so transfers
    # overlap device compute of the previous batch
    q: queue.Queue = queue.Queue(maxsize=4)
    stop = threading.Event()

    def producer() -> None:
        while not stop.is_set():
            blk = host_pool.get()
            gen_into(blk[0])
            while not stop.is_set():
                try:
                    q.put(blk, timeout=0.05)
                    break
                except queue.Full:
                    continue

    prod = threading.Thread(target=producer, daemon=True)
    prod.start()

    # Sync every 4 steps: bounds the async dispatch backlog (the update
    # donates its input, so only the newest bundle is safe to block on)
    # while leaving the pipeline full between syncs — wall clock honestly
    # covers device completion, not just dispatch.
    steps = 0
    t0 = time.perf_counter()
    deadline = t0 + cfg["bench_seconds"]
    while time.perf_counter() < deadline:
        blk = q.get()
        (k,) = stager.stage(blk, (blk[0],))
        bundle, tok = step(bundle, k, mask)
        stager.fence(tok)
        steps += 1
        if steps % 4 == 0:
            jax.block_until_ready(bundle.events)
    jax.block_until_ready(bundle.events)
    dt = time.perf_counter() - t0
    stop.set()
    try:
        q.get_nowait()  # unblock a producer stuck on put
    except queue.Empty:
        pass
    prod.join(timeout=2.0)
    stager.drain()
    e2e_ev_per_s = steps * batch / dt

    # ---- secondary: device-plane-only (pre-staged arrays) -----------------
    scratch = np.empty(batch, dtype=np.uint32)

    def staged() -> "jnp.ndarray":
        gen_into(scratch)
        return jnp.asarray(np.array(scratch))  # private copy per entry

    pool = [staged() for _ in range(8)]
    dbundle = new_bundle()
    for i in range(3):
        dbundle, _ = step(dbundle, pool[i % 8], mask)
    jax.block_until_ready(dbundle.events)
    dsteps = 0
    t0 = time.perf_counter()
    while True:
        dbundle, _ = step(dbundle, pool[dsteps % 8], mask)
        dsteps += 1
        if dsteps % 8 == 0:
            jax.block_until_ready(dbundle.events)
            if time.perf_counter() - t0 >= cfg["device_seconds"]:
                break
    jax.block_until_ready(dbundle.events)
    device_ev_per_s = dsteps * batch / (time.perf_counter() - t0)

    # ---- secondary: sharded device plane (--chips N) ----------------------
    # the shard_map step over an N-lane (node) mesh on pre-staged arrays:
    # per-round events = batch (split across lanes), so the ratio vs the
    # single-chip device plane above isolates what the sharding machinery
    # costs/buys at this scale point.
    out_sharded: dict = {}
    if chips > 1:
        ndev = len(jax.devices())
        if ndev < chips or batch % chips:
            raise SystemExit(
                f"bench: --chips {chips}: this process sees {ndev} "
                f"device(s), and batch {batch} % chips must be 0")
        from jax.sharding import NamedSharding, PartitionSpec as P

        from inspektor_gadget_tpu.ops.sketches import (
            bundle_stack_sharded, make_bundle_harvest_sharded,
            make_bundle_ingest_sharded)
        from inspektor_gadget_tpu.parallel.mesh import NODE_AXIS, ingest_mesh
        lane_n = batch // chips
        mesh = ingest_mesh(chips)
        like = new_bundle()
        sstep = make_bundle_ingest_sharded(mesh, like)
        sharvest = make_bundle_harvest_sharded(mesh, like)
        stacked = bundle_stack_sharded(like, mesh)
        sh = NamedSharding(mesh, P(NODE_AXIS))
        gen_into(scratch)
        keys = jax.device_put(
            np.tile(scratch[:lane_n], chips).reshape(chips, lane_n), sh)
        wts = jax.device_put(np.ones((chips, lane_n), np.uint32), sh)
        dr = jax.device_put(np.zeros((chips,), np.float32), sh)
        stacked, stok = sstep(stacked, keys, keys, keys, wts, dr)
        jax.block_until_ready(stok)
        ssteps = 0
        t0 = time.perf_counter()
        while True:
            stacked, stok = sstep(stacked, keys, keys, keys, wts, dr)
            ssteps += 1
            if ssteps % 8 == 0:
                jax.block_until_ready(stok)
                if time.perf_counter() - t0 >= cfg["device_seconds"]:
                    break
        jax.block_until_ready(stok)
        sharded_ev_per_s = ssteps * batch / (time.perf_counter() - t0)
        jax.block_until_ready(sharvest(stacked).events)
        out_sharded = {"chips": chips,
                       "device_plane_sharded_ev_per_s":
                           round(sharded_ev_per_s, 1)}

    # ---- secondary: single-chip merge latency -----------------------------
    merge_jit = jax.jit(bundle_merge)
    other = new_bundle()
    m = merge_jit(bundle, other)
    jax.block_until_ready(m.events)
    times = []
    for _ in range(cfg["merges"]):
        t0 = time.perf_counter()
        m = merge_jit(bundle, other)
        jax.block_until_ready(m.events)
        times.append(time.perf_counter() - t0)

    return {
        "e2e_ev_per_s": round(e2e_ev_per_s, 1),
        "host_plane_ev_per_s": round(host_ev_per_s, 1),
        "device_plane_ev_per_s": round(device_ev_per_s, 1),
        "merge_ms_p50": round(float(np.percentile(times, 50) * 1000), 3),
        "batch": batch,
        "steps": steps,
        "gen_impl": gen_impl,
        **out_sharded,
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="bench.py")
    ap.add_argument("--platform", default="auto",
                    choices=("auto", "tpu", "cpu"))
    ap.add_argument("--ledger", default=None,
                    help="also append a provenance-stamped PerfRecord here")
    ap.add_argument("--chips", type=int, default=1)
    args = ap.parse_args(argv)

    from inspektor_gadget_tpu.utils.compile_cache import ensure_compile_cache
    from inspektor_gadget_tpu.utils.platform_probe import (
        PlatformUnavailable, acquire_platform,
    )
    ensure_compile_cache()
    try:
        acq = acquire_platform(args.platform)
    except PlatformUnavailable as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    platform = acq["platform"]
    result = run(args.chips)

    from inspektor_gadget_tpu.telemetry import snapshot
    metric, unit = "sketch_ingest_throughput_e2e", "events/sec/chip"
    if platform != "tpu":
        # a number from another backend never sits under the device
        # metric's name
        metric, unit = f"{metric}_{platform}", "events/sec"
    value = result.pop("e2e_ev_per_s")
    extra = {
        "platform": platform,
        "device_kind": acq["device_kind"],
        "device_count": acq["device_count"],
        "pipeline": (f"pop_folded({result['gen_impl']})->pinned-pool"
                     "->h2d_overlap(depth4)->ingest_step"),
        **result,
        "telemetry": snapshot(),
    }
    record = {
        "metric": metric,
        "value": value,
        "unit": unit,
        "vs_baseline": round(value / BASELINE_EV_S, 3),
        "extra": extra,
    }
    print(json.dumps(record))
    if args.ledger:
        _append_ledger(record, acq, args.ledger)
    return 0


def _append_ledger(record: dict, acquired: dict, path: str) -> None:
    from inspektor_gadget_tpu.perf import append_record, make_record
    from inspektor_gadget_tpu.perf.provenance import (
        build_provenance, probe_block,
    )
    extra = record["extra"]
    # stage names of the staged pipeline: the host plane IS the folded
    # exporter and the device plane the ingest step; the config stays
    # "bench.e2e" so compare never forks the series vs old records
    stages = {
        "pop_folded": {"ev_per_s": extra["host_plane_ev_per_s"]},
        "fused_update": {"ev_per_s": extra["device_plane_ev_per_s"]},
        "merge": {"ms_p50": extra["merge_ms_p50"]},
    }
    prov = build_provenance(extra["platform"], probe_block(acquired))
    rec = make_record(
        config="bench.e2e", metric=record["metric"], unit=record["unit"],
        value=record["value"], stages=stages, provenance=prov,
        telemetry=extra["telemetry"],
        extra={"batch": extra["batch"],
               "vs_baseline": record["vs_baseline"]})
    append_record(rec, path)


if __name__ == "__main__":
    sys.exit(main())
