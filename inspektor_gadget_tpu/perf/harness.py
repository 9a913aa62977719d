"""Stage-segmented perf harness: run the ingest pipeline under real
tracing spans and emit a schema-validated PerfRecord.

Where bench.py produces one headline number, this harness attributes the
same pipeline to its stages, in the spirit of *Sketch Disaggregation
Across Time and Space*: a regression report that says "fold32 got 40%
slower" is actionable; "the number went down" is not. Two pipeline
shapes exist (ISSUE 10):

- ``classic``: pop → decode → enrich → fold32 → h2d → bundle_update —
  the pre-fusion hot path, kept measurable so the fused rewrite's win
  stays a ledger fact instead of a story;
- ``fused`` (default): pop_folded → h2d_overlap → fused_update — the
  native SoA exporter fills a pinned staging block with pre-folded
  uint32 keys (zero per-event Python), a depth-N stager overlaps the
  H2D transfer of batch k+1 with device compute of batch k, and all
  sketch planes update in ONE fused device step.

Both append to the SAME (config, metric, platform) ledger series — the
record's ``extra.pipeline`` string names the shape, so `bench compare`
baselines old records against new ones instead of forking the series.

Instrumentation reuses the existing telemetry plane end to end:

- every stage feeds the `ig_perf_stage_seconds{stage=...}` histogram
  (PR 1 registry) once per batch;
- the run opens a `perf/run/<config>` span and the first SPAN_BATCHES
  batches emit real child spans per stage (PR 2 tracer) — enough to see
  pipeline structure in the Chrome export without drowning the span ring
  on long runs;
- the finished record embeds `telemetry.snapshot()` and, when asked, a
  Perfetto-loadable Chrome trace of the run.

The platform is acquired FIRST, in this process
(utils/platform_probe.acquire_platform): a run asked for the TPU fails
when it does not get one, and the record's provenance names the platform
that ran.

Both pipelines prefer the seeded NATIVE synthetic source (classic pops
Event structs and pays the Python decode+fold, fused drains the folded
SoA exporter) so fused-vs-classic comparisons isolate the restructure
rather than the generator; the pure-Python source is the no-toolchain
fallback, and extra.pipeline records which implementation ran (bench.py
remains the headline-throughput instrument; its records share the same
ledger).
"""

from __future__ import annotations

import time

import numpy as np

from ..telemetry import counter, histogram, snapshot
from ..telemetry.tracing import TRACER, export_chrome
from ..utils.logger import get_logger
from ..utils.compile_cache import ensure_compile_cache
from ..utils.platform_probe import acquire_platform
from .provenance import build_provenance, probe_block
from .schema import STAGES, make_record

log = get_logger("ig-tpu.perf")

# span-per-stage only for the first N batches; histograms cover the rest
SPAN_BATCHES = 64

HARNESS_CONFIGS: dict[str, dict] = {
    # balanced default: big enough to exercise the device plane, small
    # enough to finish on the CPU backend without scaled-down shapes
    "e2e": dict(batch=1 << 16, depth=4, log2_width=14, hll_p=12,
                entropy_log2_width=10, k=64, seconds=2.0,
                harvest_every=16, sync_every=4, merges=20),
    # the bench.py TPU production shape
    "e2e-prod": dict(batch=1 << 17, depth=4, log2_width=16, hll_p=14,
                     entropy_log2_width=12, k=128, seconds=3.0,
                     harvest_every=32, sync_every=4, merges=50),
    # tier-1 smoke: completes in well under a second on one CPU core
    "tiny": dict(batch=1 << 11, depth=2, log2_width=8, hll_p=6,
                 entropy_log2_width=6, k=8, seconds=0.15,
                 harvest_every=4, sync_every=2, merges=3),
}

_tm_stage = histogram("ig_perf_stage_seconds",
                      "per-batch wall seconds by pipeline stage",
                      ("stage",))
_tm_events = counter("ig_perf_events_total",
                     "events pushed through the perf harness")
_tm_runs = counter("ig_perf_runs_total", "harness runs by config",
                   ("config",))


class _StageClock:
    """Accumulates per-stage seconds/events and feeds the telemetry
    histogram; optionally emits a real tracer span for the stage."""

    def __init__(self, parent_ctx):
        self.seconds = {s: 0.0 for s in STAGES}
        self.calls = {s: 0 for s in STAGES}
        self.samples: dict[str, list[float]] = {"harvest": [], "merge": []}
        self._parent = parent_ctx

    def stage(self, name: str, spans: bool):
        return _StageTimer(self, name, spans)


class _StageTimer:
    __slots__ = ("_clock", "_name", "_span", "_t0")

    def __init__(self, clock: _StageClock, name: str, spans: bool):
        self._clock = clock
        self._name = name
        self._span = (TRACER.span(f"perf/{name}", parent=clock._parent)
                      if spans else None)

    def __enter__(self):
        if self._span is not None:
            self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        if self._span is not None:
            self._span.__exit__(*exc)
        self._clock.seconds[self._name] += dt
        self._clock.calls[self._name] += 1
        if self._name in self._clock.samples:
            self._clock.samples[self._name].append(dt)
        _tm_stage.labels(stage=self._name).observe(dt)


def _fold32(keys64: np.ndarray) -> np.ndarray:
    k = keys64.astype(np.uint64, copy=False)
    return ((k >> np.uint64(32)) ^ (k & np.uint64(0xFFFFFFFF))).astype(
        np.uint32)


def run_harness(config: str = "e2e", *, platform: str = "auto",
                seconds: float | None = None,
                trace_out: str | None = None,
                replay: str | None = None,
                pipeline: str = "fused",
                chips: int = 1,
                invertible: bool = False,
                quantiles: bool = False) -> dict:
    """Run one harness config; returns a validated PerfRecord dict.

    `replay` points the host side at a capture journal instead of the
    synthetic source: the measured input becomes reproducible
    input-for-input (the recorded batch sequence, cycled through the
    window) and the journal's content digest lands in the record's
    provenance, so two records claiming the same replay input can be
    checked against each other.

    `pipeline` picks the hot-path shape: "fused" (pop_folded →
    h2d_overlap → fused_update, the default) or "classic" (pop → decode
    → enrich → fold32 → h2d → bundle_update, the reference path). The
    fused host side drains the NATIVE folded exporter when the capture
    library is available; otherwise it folds the pure-Python source
    inside the pop_folded stage and says so in extra.pipeline.

    `invertible` adds the invertible heavy-key plane to the bundle (the
    fused step absorbs it as extra kernel planes; the record stays in
    the SAME ledger series with extra.invertible naming the shape — the
    acceptance comparison is host-plane throughput within the baseline
    band). Two extra stages land in the record: inv_decode times a real
    decode of the live state at every harvest tick, and inv_update a
    post-loop micro-measurement of the standalone invertible update (the
    merge-stage pattern).

    `quantiles` adds the DDSketch latency plane to the bundle and a
    synthetic ns-domain value lane to the staging block (fused pipeline
    only — the value lane rides the folded SoA block). The record stays
    in the SAME ledger series with extra.quantiles naming the shape; a
    post-loop qt_update stage micro-measures the standalone DDSketch
    fold at this batch shape (the inv_update pattern).

    The caller decides whether it lands in the ledger (cli/bench.py
    appends by default; tests pass their own tmp path)."""
    cfg = HARNESS_CONFIGS.get(config)
    if cfg is None:
        raise ValueError(f"unknown harness config {config!r} "
                         f"(have: {', '.join(sorted(HARNESS_CONFIGS))})")
    if pipeline not in ("fused", "classic", "sharded"):
        raise ValueError(f"unknown pipeline {pipeline!r} "
                         "(have: fused, classic, sharded)")
    if pipeline != "sharded" and chips != 1:
        raise ValueError("--chips needs pipeline=sharded (the fused and "
                         "classic arms are single-chip by construction)")
    if pipeline == "sharded" and replay:
        raise ValueError("pipeline=sharded does not take --replay yet "
                         "(replay determinism through the sharded path is "
                         "covered by the operator tier)")
    if invertible and pipeline == "sharded":
        raise ValueError("--invertible measures the single-chip fused/"
                         "classic arms (the sharded arm's per-chip number "
                         "comes from the same fused step)")
    if quantiles and pipeline != "fused":
        raise ValueError("--quantiles measures the fused arm (the value "
                         "lane rides the folded staging block; classic "
                         "has no values input, sharded's per-chip number "
                         "comes from the same fused step)")
    _tm_runs.labels(config=config).inc()
    window = cfg["seconds"] if seconds is None else float(seconds)

    ensure_compile_cache()
    # raises PlatformUnavailable when the TPU was asked for and is absent
    acquired = acquire_platform(platform)

    import jax
    import jax.numpy as jnp

    from ..ops import bundle_merge, topk_values, hll_estimate, entropy_estimate
    from ..ops.sketches import bundle_ingest_jit, bundle_init, bundle_update_jit
    from ..sources.synthetic import PySyntheticSource

    actual = acquired["platform"]

    if pipeline == "sharded":
        return _run_sharded(config, cfg, window, chips, acquired, actual,
                            platform, trace_out)

    batch_n = cfg["batch"]
    replay_src = None
    if replay:
        from ..capture.replay import ReplaySource
        replay_src = ReplaySource(replay, cycle=True)
        if not len(replay_src):
            raise ValueError(f"{replay}: journal carries no batches to "
                             "replay through the harness")
        src = replay_src
        batch_n = max(b.capacity for b in replay_src.batches)
    else:
        src = PySyntheticSource(seed=42, vocab=5000, batch_size=batch_n)

    # both pipelines prefer the native synthetic source so the fused-vs-
    # classic comparison isolates the RESTRUCTURE, not the generator:
    # classic pops C++ Event structs and pays the Python decode+fold
    # (the pre-PR hot path), fused drains the folded SoA exporter. The
    # pure-Python source is the no-toolchain fallback for either, and
    # extra.pipeline records which implementation ran.
    native_gen = None
    if replay_src is None:
        try:
            from ..sources.bridge import (SRC_SYNTH_EXEC, NativeCapture,
                                          native_available)
            if native_available():
                native_gen = NativeCapture(SRC_SYNTH_EXEC, seed=42,
                                           vocab=5000, zipf_s=1.2)
        except (OSError, RuntimeError, ValueError) as e:
            log.debug("native synthetic source unavailable (%r); "
                      "pure-python fallback", e)
            native_gen = None

    inv_rows = 3 if invertible else 0
    inv_lb = min(12, cfg["log2_width"]) if invertible else 12

    def new_bundle():
        return bundle_init(depth=cfg["depth"], log2_width=cfg["log2_width"],
                           hll_p=cfg["hll_p"],
                           entropy_log2_width=cfg["entropy_log2_width"],
                           k=cfg["k"], inv_rows=inv_rows,
                           inv_log2_buckets=inv_lb, quantiles=quantiles)

    # synthetic ns-domain latencies for the value lane: precomputed once,
    # copied into the pinned block per batch — the same host cost the
    # operator pays filling the lane from a batch column
    qt_lat = None
    if quantiles:
        from .quantile_bench import _latencies
        qt_lat = np.minimum(_latencies(batch_n),
                            np.float32(0xFFFFFFFF)).astype(np.uint32)

    # the shared staged-ingest step (update + fence token + weights-lane
    # semantics — the donation/fence contract is documented once, on
    # ops.sketches.bundle_ingest_step)
    def fused_step(bundle, k, w, v=None):
        if quantiles:
            return bundle_ingest_jit(bundle, k, k, k, w, None, v)
        return bundle_ingest_jit(bundle, k, k, k, w)

    with TRACER.span(f"perf/run/{config}",
                     attrs={"config": config, "platform": actual,
                            "batch": batch_n,
                            "pipeline": pipeline}) as run_span:
        clock = _StageClock(run_span.context)

        pool = stager = pstats = None
        if pipeline == "fused":
            from ..sources.staging import H2DStager, PinnedBufferPool
            from ..telemetry.pipeline import PipelineStats
            pool = PinnedBufferPool(batch_n, lanes=3 if quantiles else 2,
                                    max_free=4)
            # pipeline health plane (ISSUE 18): the harness runs the SAME
            # instrumented stager as the operator, so the record carries
            # starved-fraction + per-stage lag quantiles — BENCH_r04's
            # starvation gap as a ledger series, not a one-off anecdote
            pstats = PipelineStats(f"perf.{config}")
            stager = H2DStager(pool, depth=2, stats=pstats)

        # warm: compile + source ramp, outside every measured window.
        # Replay journals may carry heterogeneous batch shapes, and each
        # distinct shape is a fresh XLA compile — warm them ALL here or
        # the compile lands inside the measured window (the exact
        # non-reproducibility --replay exists to eliminate). The fused
        # pipeline re-pads every batch into one fixed-capacity pinned
        # block, so it compiles exactly ONE shape regardless of input.
        bundle = new_bundle()
        if replay_src is not None:
            warm_batches = list({b.capacity: b
                                 for b in replay_src.batches}.values())
        elif native_gen is not None and pipeline == "classic":
            warm_batches = [native_gen.generate(batch_n)]
        else:
            warm_batches = [src.generate(batch_n)]
        if pipeline == "fused":
            blk = pool.get()
            if native_gen is not None:
                native_gen.generate_folded(batch_n, out=blk[0])
            else:
                wb = warm_batches[0]
                wk = _fold32(np.asarray(wb.cols["key_hash"][:wb.count],
                                        dtype=np.uint64))
                blk[0][:wk.size] = wk
                blk[0][wk.size:] = 0
            blk[1][:] = 1
            if quantiles:
                blk[2][:] = qt_lat
                k_d, w_d, v_d = stager.stage(blk, (blk[0], blk[1], blk[2]))
                for _ in range(2):
                    bundle, _tok = fused_step(bundle, k_d, w_d, v_d)
            else:
                k_d, w_d = stager.stage(blk, (blk[0], blk[1]))
                for _ in range(2):
                    bundle, _tok = fused_step(bundle, k_d, w_d)
            jax.block_until_ready(bundle.events)
            stager.drain()
        else:
            for warm in warm_batches:
                wk = jnp.asarray(_fold32(np.asarray(warm.cols["key_hash"])))
                wm = jnp.asarray(warm.mask())
                for _ in range(2):
                    bundle = bundle_update_jit(bundle, wk, wk, wk, wm)
            jax.block_until_ready(bundle.events)
        if replay_src is not None:
            replay_src.reset()  # measure the recorded sequence from 0
            bundle = new_bundle()

        steps = 0
        events = 0
        drops = 0
        t_loop = time.perf_counter()
        deadline = t_loop + window
        while time.perf_counter() < deadline:
            spans = steps < SPAN_BATCHES
            if pipeline == "fused":
                t_gen = time.perf_counter()
                with clock.stage("pop_folded", spans):
                    block = pool.get()
                    if native_gen is not None:
                        # native exporter fills the pinned lane directly:
                        # no Event structs, no decode, no fold pass
                        native_gen.generate_folded(batch_n, out=block[0])
                        n = batch_n
                        block[1][:] = 1
                    else:
                        b = src.generate(batch_n)
                        n = b.count
                        k32 = _fold32(np.asarray(b.cols["key_hash"][:n],
                                                 dtype=np.uint64))
                        block[0][:n] = k32
                        block[0][n:] = 0
                        block[1][:n] = 1
                        block[1][n:] = 0
                        drops += b.drops
                    if quantiles:
                        block[2][:] = qt_lat
                t_pop = time.perf_counter()
                with clock.stage("h2d_overlap", spans):
                    # async device put; overlaps the previous batch's
                    # fused_update, blocks only when >= depth ahead
                    if quantiles:
                        k, w, v = stager.stage(
                            block, (block[0], block[1], block[2]))
                    else:
                        k, w = stager.stage(block, (block[0], block[1]))
                        v = None
                # batch-grain watermarks, same clocks the operator uses:
                # host lag = pop − generation, device lag = dispatch − pop
                pstats.note_host_lag(t_pop - t_gen)
                pstats.note_device_lag(time.perf_counter() - t_pop)
                with clock.stage("fused_update", spans):
                    bundle, tok = fused_step(bundle, k, w, v)
                    stager.fence(tok)
                    if (steps + 1) % cfg["sync_every"] == 0:
                        jax.block_until_ready(bundle.events)
            else:
                with clock.stage("pop", spans):
                    batch = (native_gen.generate(batch_n)
                             if native_gen is not None
                             else src.generate(batch_n))
                with clock.stage("decode", spans):
                    keys64 = np.ascontiguousarray(
                        np.asarray(batch.cols["key_hash"], dtype=np.uint64))
                with clock.stage("enrich", spans):
                    mask_np = batch.mask()
                    drops += batch.drops
                with clock.stage("fold32", spans):
                    k32 = _fold32(keys64)
                with clock.stage("h2d", spans):
                    k = jnp.asarray(k32)
                    mask = jnp.asarray(mask_np)
                with clock.stage("bundle_update", spans):
                    bundle = bundle_update_jit(bundle, k, k, k, mask)
                    # bound the async backlog so wall clock covers device
                    # completion, not just dispatch (bench.py's honesty rule)
                    if (steps + 1) % cfg["sync_every"] == 0:
                        jax.block_until_ready(bundle.events)
                n = batch.count
            steps += 1
            events += n
            _tm_events.inc(n)
            if steps % cfg["harvest_every"] == 0:
                with clock.stage("harvest", spans):
                    hh_keys, hh_counts = topk_values(bundle.topk)
                    np.asarray(hh_counts)
                    float(hll_estimate(bundle.hll))
                    float(entropy_estimate(bundle.entropy))
                if invertible:
                    # a REAL decode of the live merged state per harvest
                    # tick — the cost a consumer of decoded heavy keys
                    # actually pays (device peel + host finisher)
                    with clock.stage("inv_decode", spans):
                        from ..ops.invertible import inv_decode
                        inv_decode(bundle.inv, device_sweeps=2, cap=512)
        final_stage = "fused_update" if pipeline == "fused" else "bundle_update"
        with clock.stage(final_stage, steps < SPAN_BATCHES):
            jax.block_until_ready(bundle.events)
            if stager is not None:
                stager.drain()
        elapsed = time.perf_counter() - t_loop
        if native_gen is not None:
            native_gen.close()

        # merge latency at this config's shape (cluster wire plane)
        merge_jit = jax.jit(bundle_merge)
        other = new_bundle()
        jax.block_until_ready(merge_jit(bundle, other).events)  # compile
        for _ in range(cfg["merges"]):
            with clock.stage("merge", True):
                jax.block_until_ready(merge_jit(bundle, other).events)

        if invertible:
            # standalone invertible update at this batch shape (the
            # post-loop micro-measurement pattern the merge stage uses):
            # on the hot path the fused kernel absorbs these planes, so
            # this isolates what the plane itself costs per batch
            from ..ops.invertible import inv_init, inv_update
            inv_step = jax.jit(inv_update, donate_argnums=0)
            inv_s = inv_init(inv_rows, inv_lb)
            ik = jnp.asarray(np.arange(1, batch_n + 1, dtype=np.uint32))
            iw = jnp.ones(batch_n, jnp.int32)
            inv_s = inv_step(inv_s, ik, iw)
            jax.block_until_ready(inv_s.count)  # compile
            for _ in range(cfg["merges"]):
                with clock.stage("inv_update", True):
                    inv_s = inv_step(inv_s, ik, iw)
                    jax.block_until_ready(inv_s.count)

        if quantiles:
            # standalone DDSketch fold at this batch shape (the
            # inv_update pattern): the fused kernel absorbs the plane on
            # the hot path, so this isolates what it costs per batch
            from ..ops.quantiles import dd_init, dd_update
            qt_step = jax.jit(dd_update, donate_argnums=0)
            qt_s = dd_init(0.01, 2048, min_value=1.0)
            qv = jnp.asarray(qt_lat.astype(np.float32))
            qt_s = qt_step(qt_s, qv)
            jax.block_until_ready(qt_s.counts)  # compile
            for _ in range(cfg["merges"]):
                with clock.stage("qt_update", True):
                    qt_s = qt_step(qt_s, qv)
                    jax.block_until_ready(qt_s.counts)

        # accuracy audit plane cost at this batch shape (ISSUE 19): a
        # post-loop micro-measurement of the bottom-k shadow-sample fold
        # (the merge-stage pattern), projected onto this run's measured
        # wall clock — extra.audit_overhead is the fraction of ingest
        # time `audit-sample > 0` would have added at this config, the
        # same quantity perf/accuracy_bench.py's dedicated series gates.
        from ..ops.accuracy import ShadowSample
        audit_keys64 = np.arange(1, batch_n + 1, dtype=np.uint64) * np.uint64(
            0x9E3779B97F4A7C15)
        audit_sh = ShadowSample(1024)
        audit_sh.update(_fold32(audit_keys64))  # warm: fill the reservoir
        audit_reps = max(int(cfg["merges"]), 8)
        t_a = time.perf_counter()
        for _ in range(audit_reps):
            with clock.stage("audit_feed", True):
                audit_sh.update(_fold32(audit_keys64))
        audit_s = max(time.perf_counter() - t_a, 1e-9)
        audit_proj = (audit_s / audit_reps) * max(steps, 1)
        audit_overhead = audit_proj / max(elapsed + audit_proj, 1e-9)

        run_span.set_attr("events", events)
        run_span.set_attr("ev_per_s", round(events / max(elapsed, 1e-9), 1))
        trace_id = run_span.context.trace_id

    value = events / max(elapsed, 1e-9)
    stages: dict[str, dict[str, float]] = {}
    for s in STAGES:
        if clock.calls[s] == 0:
            continue
        st: dict[str, float] = {
            "seconds": round(clock.seconds[s], 6),
            "calls": clock.calls[s],
        }
        if s in ("pop", "decode", "enrich", "fold32", "pop_folded", "h2d",
                 "h2d_overlap", "bundle_update", "fused_update"):
            st["ev_per_s"] = round(
                events / max(clock.seconds[s], 1e-9), 1)
        if clock.samples.get(s):
            ms = np.asarray(clock.samples[s]) * 1000.0
            st["ms_p50"] = round(float(np.percentile(ms, 50)), 3)
            st["ms_p95"] = round(float(np.percentile(ms, 95)), 3)
        stages[s] = st

    trace_file = None
    if trace_out:
        import json as _json
        doc = export_chrome(TRACER.export(trace_id=trace_id))
        with open(trace_out, "w", encoding="utf-8") as f:
            f.write(_json.dumps(doc, default=str))
        trace_file = trace_out

    prov = build_provenance(actual, probe_block(acquired))
    extra_fields: dict = {}
    # pipeline provenance: the stage list names the shape that ran, and
    # the host-plane aggregate is the acceptance comparison's numerator
    # (pop_folded→h2d vs pop→decode→enrich→fold32→h2d stage totals)
    from .schema import HOST_STAGES
    host_secs = sum(clock.seconds[s] for s in HOST_STAGES[pipeline])
    extra_fields["host_plane_ev_per_s"] = round(
        events / max(host_secs, 1e-9), 1)
    impl = ("native" if native_gen is not None
            else "replay" if replay_src is not None else "py")
    inv_tag = ("+inv" if invertible else "") + ("+qt" if quantiles else "")
    if pipeline == "fused":
        extra_fields["pipeline"] = (
            f"pop_folded({'py-fold' if impl == 'py' else impl})"
            f"->h2d_overlap(depth2)->fused_update{inv_tag}")
    else:
        extra_fields["pipeline"] = (
            f"pop({impl})->decode->enrich->fold32->h2d"
            f"->bundle_update{inv_tag}")
    if invertible:
        extra_fields["invertible"] = True
        extra_fields["inv_geometry"] = f"{inv_rows}x2^{inv_lb}"
    if quantiles:
        extra_fields["quantiles"] = True
        extra_fields["qt_geometry"] = "2048@alpha0.01"
    # the audit plane's relative feed cost vs the staging copy it rides
    extra_fields["audit_overhead"] = round(audit_overhead, 4)
    if pstats is not None:
        psnap = pstats.snapshot()
        pstats.unregister()  # return the shared gauges to baseline
        extra_fields["starved_fraction"] = round(psnap["starved_ratio"], 4)
        extra_fields["stall_s"] = round(psnap["stall_s"], 6)
        extra_fields["stage_lag"] = {
            stage: {"p50_s": round(row["p50_s"], 9),
                    "p99_s": round(row["p99_s"], 9)}
            for stage, row in psnap["stages"].items()}
    if replay_src is not None:
        # the journal digest IS part of the number's meaning: same
        # config + same digest → directly comparable records
        prov["replay"] = {"journal": replay, "digest": replay_src.digest,
                          "batches": len(replay_src)}
        extra_fields["replay_digest"] = replay_src.digest
    rec = make_record(
        config=f"harness.{config}",
        metric="sketch_ingest_throughput_e2e",
        unit="events/sec/chip",
        value=round(value, 1),
        stages=stages,
        provenance=prov,
        telemetry=snapshot(),
        extra={"batch": batch_n, "steps": steps, "events": events,
               "drops": drops, "elapsed_s": round(elapsed, 3),
               "window_s": window, "trace_id": trace_id,
               "requested_platform": platform, **extra_fields},
        trace_file=trace_file,
    )
    log.info("harness %s: %.1f ev/s on %s (%d events, %d steps)",
             config, value, actual, events, steps)
    return rec


def _run_sharded(config: str, cfg: dict, window: float, chips: int,
                 acquired: dict, actual: str, platform: str,
                 trace_out: str | None) -> dict:
    """The ISSUE-14 chips-scaling arm: pop_folded → h2d_lanes →
    sharded_update over a (node) mesh of `chips` local devices. The
    config batch SPLITS across lanes (lane batch = batch/chips, loudly
    validated), so every scale point pushes the same events per round
    and the curve isolates the sharding, not the batch shape.

    The headline value is the DEVICE-PLANE AGGREGATE: per-chip update
    throughput (BENCH_r04's device-plane loop, measured on one lane's
    shape in isolation) × chips. Lanes share no hot-path state — the
    sharded step runs each chip's fused update with zero cross-chip
    traffic — so the aggregate is the capacity concurrent lanes expose.
    On a CPU *simulation* the virtual devices timeshare the host's
    cores, so the record also carries the honest serialized wall-clock
    numbers (extra.e2e_wall_ev_per_s, extra.device_plane_wall_ev_per_s)
    and names the aggregation formula in extra.aggregation; docs quoting
    the curve must label it CPU/simulated (tools/check_perf_claims.py
    enforces the labeling).
    """
    import jax

    from ..ops.sketches import (bundle_digest_jit, bundle_ingest_jit,
                                bundle_init, bundle_stack_sharded,
                                make_bundle_harvest_sharded,
                                make_bundle_ingest_sharded)
    from ..parallel.mesh import NODE_AXIS, ingest_mesh
    from ..sources.staging import H2DStager, PinnedBufferPool
    from ..sources.synthetic import PySyntheticSource

    ndev = len(jax.devices())
    if not 1 <= chips <= ndev:
        raise ValueError(f"chips={chips} out of range for this host "
                         f"({ndev} local device(s))")
    batch_n = cfg["batch"]
    if batch_n % chips:
        raise ValueError(f"config batch {batch_n} is not divisible by "
                         f"chips={chips} — lanes need equal SoA shards")
    lane_n = batch_n // chips
    mesh = ingest_mesh(chips)
    devices = list(mesh.devices.reshape(-1))
    like = bundle_init(depth=cfg["depth"], log2_width=cfg["log2_width"],
                       hll_p=cfg["hll_p"],
                       entropy_log2_width=cfg["entropy_log2_width"],
                       k=cfg["k"])
    step = make_bundle_ingest_sharded(mesh, like)
    harvest = make_bundle_harvest_sharded(mesh, like)
    stacked = bundle_stack_sharded(like, mesh)

    from jax.sharding import NamedSharding, PartitionSpec as P
    sh = NamedSharding(mesh, P(NODE_AXIS))

    native_gen = None
    try:
        from ..sources.bridge import (SRC_SYNTH_EXEC, NativeCapture,
                                      native_available)
        if native_available():
            native_gen = NativeCapture(SRC_SYNTH_EXEC, seed=42,
                                       vocab=5000, zipf_s=1.2)
    except (OSError, RuntimeError, ValueError) as e:
        log.debug("native synthetic source unavailable (%r); "
                  "pure-python fallback", e)
    src = None if native_gen is not None else PySyntheticSource(
        seed=42, vocab=5000, batch_size=lane_n)

    pools = [PinnedBufferPool(lane_n, lanes=2, max_free=4, lane=k)
             for k in range(chips)]
    stagers = [H2DStager(pools[k], depth=2, device=devices[k])
               for k in range(chips)]
    zeros_drops = jax.make_array_from_single_device_arrays(
        (chips,), sh, [jax.device_put(np.zeros(1, np.float32), d)
                       for d in devices])

    def fill_block(block) -> None:
        if native_gen is not None:
            native_gen.generate_folded(lane_n, out=block[0])
        else:
            b = src.generate(lane_n)
            block[0][:b.count] = _fold32(np.asarray(
                b.cols["key_hash"][:b.count], dtype=np.uint64))
            block[0][b.count:] = 0
        block[1][:] = 1

    def stage_round():
        parts = []
        for k in range(chips):
            block = pools[k].get()
            fill_block(block)
            parts.append(stagers[k].stage(block, (block[0], block[1])))
        keys = jax.make_array_from_single_device_arrays(
            (chips, lane_n), sh, [p[0].reshape(1, -1) for p in parts])
        wts = jax.make_array_from_single_device_arrays(
            (chips, lane_n), sh, [p[1].reshape(1, -1) for p in parts])
        return keys, wts

    # warm: compile the sharded step + harvest outside the window
    keys, wts = stage_round()
    stacked, tok = step(stacked, keys, keys, keys, wts, zeros_drops)
    jax.block_until_ready(tok)
    jax.block_until_ready(harvest(stacked).events)
    for st in stagers:
        st.drain()

    with TRACER.span(f"perf/run/{config}",
                     attrs={"config": config, "platform": actual,
                            "batch": batch_n, "pipeline": "sharded",
                            "chips": chips}) as run_span:
        clock = _StageClock(run_span.context)
        steps_n = 0
        events = 0
        t_loop = time.perf_counter()
        deadline = t_loop + window
        while time.perf_counter() < deadline:
            spans = steps_n < SPAN_BATCHES
            with clock.stage("pop_folded", spans):
                parts = []
                for k in range(chips):
                    block = pools[k].get()
                    fill_block(block)
                    parts.append((block, k))
            with clock.stage("h2d_lanes", spans):
                staged = [stagers[k].stage(b, (b[0], b[1]))
                          for b, k in parts]
                keys = jax.make_array_from_single_device_arrays(
                    (chips, lane_n), sh,
                    [p[0].reshape(1, -1) for p in staged])
                wts = jax.make_array_from_single_device_arrays(
                    (chips, lane_n), sh,
                    [p[1].reshape(1, -1) for p in staged])
            with clock.stage("sharded_update", spans):
                stacked, tok = step(stacked, keys, keys, keys, wts,
                                    zeros_drops)
                for st in stagers:
                    st.fence(tok)
                if (steps_n + 1) % cfg["sync_every"] == 0:
                    jax.block_until_ready(tok)
            steps_n += 1
            events += batch_n
            _tm_events.inc(batch_n)
            if steps_n % cfg["harvest_every"] == 0:
                with clock.stage("harvest", spans):
                    merged = harvest(stacked)
                    jax.block_until_ready(
                        bundle_digest_jit(merged))
        with clock.stage("sharded_update", steps_n < SPAN_BATCHES):
            jax.block_until_ready(tok)
            for st in stagers:
                st.drain()
        elapsed = time.perf_counter() - t_loop

        # device-plane loops on pre-staged arrays (no host generation):
        # (a) one lane's fused update in isolation — the per-chip number
        # every scale point shares; (b) the sharded step's wall rate —
        # what this host's serialized simulation actually sustains
        # floor the device-plane windows at 0.5s: the tiny config's
        # 0.15s window under-samples the loop (first sync swallows the
        # leftover async tail) and publishes noise
        dev_win = max(min(window, 1.0), 0.5)
        scratch = np.empty(lane_n, dtype=np.uint32)
        if native_gen is not None:
            native_gen.generate_folded(lane_n, out=scratch)
        else:
            scratch[:] = np.arange(1, lane_n + 1, dtype=np.uint32)
        one_keys = jax.device_put(np.array(scratch), devices[0])
        one_w = jax.device_put(np.ones(lane_n, np.uint32), devices[0])
        dbundle = like
        dbundle, dtok = bundle_ingest_jit(dbundle, one_keys, one_keys,
                                          one_keys, one_w)
        jax.block_until_ready(dtok)
        dsteps = 0
        t0 = time.perf_counter()
        while True:
            dbundle, dtok = bundle_ingest_jit(dbundle, one_keys, one_keys,
                                              one_keys, one_w)
            dsteps += 1
            if dsteps % 8 == 0:
                jax.block_until_ready(dtok)
                if time.perf_counter() - t0 >= dev_win:
                    break
        jax.block_until_ready(dtok)
        per_chip = dsteps * lane_n / (time.perf_counter() - t0)

        keys, wts = stage_round()
        wsteps = 0
        t0 = time.perf_counter()
        while True:
            stacked, tok = step(stacked, keys, keys, keys, wts,
                                zeros_drops)
            wsteps += 1
            if wsteps % 8 == 0:
                jax.block_until_ready(tok)
                if time.perf_counter() - t0 >= dev_win:
                    break
        jax.block_until_ready(tok)
        device_wall = wsteps * batch_n / (time.perf_counter() - t0)
        for st in stagers:
            st.drain()
        if native_gen is not None:
            native_gen.close()

        aggregate = per_chip * chips
        run_span.set_attr("events", events)
        run_span.set_attr("device_plane_aggregate_ev_per_s",
                          round(aggregate, 1))
        trace_id = run_span.context.trace_id

    stages: dict[str, dict[str, float]] = {}
    for s in STAGES:
        if clock.calls[s] == 0:
            continue
        st: dict[str, float] = {"seconds": round(clock.seconds[s], 6),
                                "calls": clock.calls[s]}
        if s in ("pop_folded", "h2d_lanes", "sharded_update"):
            st["ev_per_s"] = round(events / max(clock.seconds[s], 1e-9), 1)
        if clock.samples.get(s):
            ms = np.asarray(clock.samples[s]) * 1000.0
            st["ms_p50"] = round(float(np.percentile(ms, 50)), 3)
            st["ms_p95"] = round(float(np.percentile(ms, 95)), 3)
        stages[s] = st

    trace_file = None
    if trace_out:
        import json as _json
        doc = export_chrome(TRACER.export(trace_id=trace_id))
        with open(trace_out, "w", encoding="utf-8") as f:
            f.write(_json.dumps(doc, default=str))
        trace_file = trace_out

    prov = build_provenance(actual, probe_block(acquired))
    rec = make_record(
        config=f"harness.{config}",
        metric="sketch_ingest_device_plane_aggregate",
        unit="events/sec",
        value=round(aggregate, 1),
        stages=stages,
        provenance=prov,
        telemetry=snapshot(),
        extra={
            "batch": batch_n, "lane_batch": lane_n, "chips": chips,
            "steps": steps_n, "events": events,
            "elapsed_s": round(elapsed, 3), "window_s": window,
            "trace_id": trace_id, "requested_platform": platform,
            "pipeline": (f"pop_folded({'native' if native_gen is not None else 'py-fold'})"
                         f"->h2d_lanes(x{chips})->sharded_update"),
            "per_chip_ev_per_s": round(per_chip, 1),
            "device_plane_wall_ev_per_s": round(device_wall, 1),
            "e2e_wall_ev_per_s": round(events / max(elapsed, 1e-9), 1),
            "aggregation": ("per_chip_ev_per_s x chips (lanes share no "
                            "hot-path state; on CPU the simulated "
                            "devices timeshare the host cores — wall "
                            "rates beside this are the serialized "
                            "measurement)"),
        },
        trace_file=trace_file,
    )
    log.info("harness %s sharded x%d: %.1f ev/s aggregate (%.1f/chip, "
             "wall %.1f) on %s", config, chips, aggregate, per_chip,
             device_wall, actual)
    return rec
