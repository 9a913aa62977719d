"""BASELINE.md benchmark configs 1-5, one JSON line per config.

Reproduces the five configs from BASELINE.json on the platform JAX
reports (it lands in each record):

  1 `trace exec` single node through the LocalRuntime (registry, operator
    chain, CPU parser) with the tpusketch operator — events/sec absorbed.
  2 `trace tcpconnect` + `trace dns` style streams — HLL distinct error
    vs exact distinct count.
  3 `top file`/`top block-io` style zipf stream — streaming top-k
    heavy-hitter error vs exact top.
  4 `advise seccomp-profile` plane — per-container syscall entropy +
    autoencoder anomaly scoring throughput and separation.
  5 multi-node `trace tcp` — count-min psum merge across an 8-node mesh
    at the PRODUCTION bundle shape (virtual CPU devices stand in when
    only one real chip is present), plus the stated target workload:
    `trace exec` + `trace tcp` ingested CONCURRENTLY through one sketch
    plane with measured heavy-hitter error vs exact counts.

    python -m benchmarks.configs [--seconds 2.0]
"""

from __future__ import annotations

import argparse
import json
import os
import threading
import time

import numpy as np

DISPLAY_TARGET_EV_S = 5_000_000


class DisplayPathRegression(AssertionError):
    """Config 1d below its ≥5M ev/s floor — a gate failure, not a report."""


def _emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


def _exact_update(counter: dict, keys: np.ndarray) -> None:
    u, c = np.unique(keys, return_counts=True)
    for k, n in zip(u.tolist(), c.tolist()):
        counter[k] = counter.get(k, 0) + n


def _time_ticks(fn, sync, n: int = 30) -> tuple[float, float]:
    """Warm (compile) once, then time n calls; returns (p50_ms, p95_ms).
    sync(result) must block until the device work is done."""
    sync(fn())
    ticks = []
    for _ in range(n):
        t0 = time.perf_counter()
        sync(fn())
        ticks.append((time.perf_counter() - t0) * 1000.0)
    return (round(float(np.percentile(ticks, 50)), 3),
            round(float(np.percentile(ticks, 95)), 3))


def _hh_error(bundle, exact: dict) -> float:
    """Weighted heavy-hitter error: sum |est - true| / sum true over the
    sketch's top-k rows (the BASELINE <1% metric)."""
    from inspektor_gadget_tpu.ops import topk_values

    keys, ests = topk_values(bundle.topk)
    keys = np.asarray(keys).astype(np.uint32)
    ests = np.asarray(ests, dtype=np.float64)
    live = ests > 0
    keys, ests = keys[live], ests[live]
    if keys.size == 0:
        return float("nan")
    true = np.asarray([exact.get(int(k), 0) for k in keys], dtype=np.float64)
    denom = max(true.sum(), 1.0)
    return float(np.abs(ests - true).sum() / denom)


# ---------------------------------------------------------------------------
# config 1 — trace exec through the full local runtime
# ---------------------------------------------------------------------------

def config1_trace_exec_runtime(seconds: float) -> dict:
    import inspektor_gadget_tpu.all_gadgets  # noqa: F401
    from inspektor_gadget_tpu.gadgets import GadgetContext, get
    from inspektor_gadget_tpu.params import Collection
    from inspektor_gadget_tpu.runtime import LocalRuntime

    desc = get("trace", "exec")
    params = desc.params().to_params()
    params.set("source", "synthetic")
    params.set("rate", "20000000")  # ask for more than the plane can do
    params.set("batch-size", "65536")  # fewer python-side batch turns
    from inspektor_gadget_tpu.operators.operators import get as get_op
    op_params = Collection()
    tp = get_op("tpusketch").instance_params().to_params()
    tp.set("enable", "true")
    op_params["operator.tpusketch."] = tp
    summaries = []

    def run_once(timeout):
        # the tpusketch operator auto-attaches to trace gadgets; its
        # harvest summary (absorbed-event count) arrives via the
        # on_sketch_summary callback (operators/tpusketch.py:149,289)
        ctx = GadgetContext(desc, gadget_params=params,
                            operator_params=op_params, timeout=timeout,
                            extra={"on_sketch_summary": summaries.append})
        t0 = time.perf_counter()
        result = LocalRuntime().run_gadget(ctx)
        return result, time.perf_counter() - t0

    # Precompile the sketch-update executable for every pad shape the
    # operator can hit (enrich_batch doubles its pad to cover the pop
    # count, and each distinct shape is a fresh ~15s TPU compile that
    # must not land in the measured window).
    import jax
    import jax.numpy as jnp

    from inspektor_gadget_tpu.ops import bundle_init
    from inspektor_gadget_tpu.ops.sketches import bundle_update_jit
    pad = 4096
    while pad <= 65536:
        k = jnp.asarray(np.zeros(pad, np.uint32))
        m = jnp.asarray(np.zeros(pad, bool))
        jax.block_until_ready(bundle_update_jit(
            bundle_init(), k, k, k, m, jnp.float32(0)).events)
        pad *= 2
    run_once(1.0)  # source ramp + operator state warm
    summaries.clear()
    result, elapsed = run_once(seconds)
    events = summaries[-1].events if summaries else 0
    return {"config": 1, "name": "trace-exec-local-runtime",
            "metric": "sketch_ingest_ev_per_s", "unit": "events/sec",
            "value": round(events / max(elapsed, 1e-9), 1),
            "extra": {"events": events, "elapsed_s": round(elapsed, 3),
                      "errors": dict(result.errors() or {})}}


# ---------------------------------------------------------------------------
# config 1d — the plain DISPLAY path (what `ig-tpu trace exec` with columns
# output does): filters pushed down into the batch loop, survivors decoded
# and formatted. The VERDICT r4 target: >=5M ev/s.
# ---------------------------------------------------------------------------

def config1d_display_path(seconds: float) -> dict:
    import io

    import inspektor_gadget_tpu.all_gadgets  # noqa: F401
    from inspektor_gadget_tpu.columns import (
        TextFormatter, match_event, parse_filters,
    )
    from inspektor_gadget_tpu.gadgets import GadgetContext, get
    from inspektor_gadget_tpu.runtime import LocalRuntime

    def run_display(filter_spec: str) -> tuple[float, int]:
        desc = get("trace", "exec")
        params = desc.params().to_params()
        params.set("source", "synthetic")
        params.set("rate", "30000000")
        params.set("batch-size", "131072")
        extra = {"output": "columns"}
        ctx = GadgetContext(desc, gadget_params=params, timeout=seconds,
                            extra=extra)
        cols = ctx.columns
        cols.hide_tagged(["kubernetes"])
        filters = parse_filters(filter_spec, cols) if filter_spec else []
        if filters:
            extra["display_filters"] = filters
            extra["display_columns"] = cols
        formatter = TextFormatter(cols)
        out = io.StringIO()
        shown = [0]
        ingested = [0]

        def on_event(ev):
            # exact CLI handler shape (cli/main.py cmd_run on_event)
            if (filters and not extra.get("display_filters_applied")
                    and not match_event(ev, filters, cols)):
                return
            shown[0] += 1
            out.write(formatter.format_event(ev) + "\n")
            if shown[0] % 65536 == 0:
                # the unfiltered variant formats EVERY row; cap the sink
                # so a long window doesn't hold gigabytes of rendered text
                out.seek(0)
                out.truncate(0)

        def on_batch(b):
            ingested[0] += b.count

        t0 = time.perf_counter()
        result = LocalRuntime().run_gadget(ctx, on_event=on_event,
                                           on_batch=on_batch)
        elapsed = time.perf_counter() - t0
        errs = result.errors()
        if errs:
            raise RuntimeError(str(errs))
        return ingested[0] / max(elapsed, 1e-9), shown[0]

    rate_comm, shown_comm = run_display("comm:proc-42")
    rate_pid, _ = run_display("pid:>4000000000")
    # high-match filtered variant: the filter is pushed down but matches
    # (nearly) every row, so every survivor still decodes + formats — the
    # pushdown machinery's overhead with none of its selectivity win.
    rate_hi, shown_hi = run_display("pid:>0")
    # unfiltered variant: every popped row decodes + formats (match rate
    # 100%) — the honest ceiling of the render path. The ≥5M ev/s claim is
    # the FILTERED path (filters pushed down columnar, survivors only);
    # all variants land in the record so none masquerades as another.
    rate_all, shown_all = run_display("")
    value = round(min(rate_comm, rate_pid), 1)
    rec = {"config": "1d", "name": "trace-exec-display-path",
           "metric": "display_ingest_ev_per_s", "unit": "events/sec",
           "value": value,
           "extra": {"comm_filter_ev_per_s": round(rate_comm, 1),
                     "numeric_filter_ev_per_s": round(rate_pid, 1),
                     "highmatch_filter_ev_per_s": round(rate_hi, 1),
                     "unfiltered_ev_per_s": round(rate_all, 1),
                     "rows_shown_comm": shown_comm,
                     "rows_shown_highmatch": shown_hi,
                     "rows_shown_unfiltered": shown_all,
                     "note": "value/target are the low-match filtered "
                             "display path; highmatch_filter_ev_per_s "
                             "pays pushdown with ~100% survivors and "
                             "unfiltered_ev_per_s formats every row",
                     "target": DISPLAY_TARGET_EV_S}}
    # GUARDRAIL (VERDICT Weak #5): the ≥5M filtered-path claim is a
    # gate, not a report — a run below target must FAIL the config (and
    # the process exit, see main) instead of quietly emitting a low
    # number for a human to overlook. IG_BENCH_NO_GATE=1 demotes the
    # gate to a report for exploratory runs on slow hosts.
    if (value < DISPLAY_TARGET_EV_S
            and os.environ.get("IG_BENCH_NO_GATE", "") != "1"):
        raise DisplayPathRegression(
            f"config 1d filtered display path {value:,.0f} ev/s is below "
            f"the {DISPLAY_TARGET_EV_S:,} ev/s target "
            f"(comm={rate_comm:,.0f}, pid={rate_pid:,.0f}); "
            f"record: {json.dumps(rec)}")
    return rec


# ---------------------------------------------------------------------------
# config 2 — HLL distinct on connect/dns-style streams
# ---------------------------------------------------------------------------

def config2_hll_distinct(seconds: float) -> dict:
    import jax.numpy as jnp

    from inspektor_gadget_tpu.ops import bundle_init, hll_estimate
    from inspektor_gadget_tpu.ops.sketches import bundle_update_jit

    rng = np.random.default_rng(2)
    batch = 1 << 16
    bundle = bundle_init()
    mask = jnp.ones(batch, dtype=bool)
    # compile outside the window (first TPU compile would eat it whole)
    warm = jnp.asarray(np.zeros(batch, np.uint32))
    import jax
    jax.block_until_ready(
        bundle_update_jit(bundle_init(), warm, warm, warm, mask).events)
    seen: set = set()
    deadline = time.monotonic() + seconds
    total = 0
    while time.monotonic() < deadline:
        # (saddr,daddr,dport) tuples and qnames, pre-hashed to uint32 —
        # a heavy-tailed population with ~200k live distincts
        keys = rng.integers(1, 200_000, batch).astype(np.uint32)
        keys = (keys * np.uint32(2654435761)) ^ np.uint32(0x9E3779B9)
        seen.update(np.unique(keys).tolist())
        k = jnp.asarray(keys)
        bundle = bundle_update_jit(bundle, k, k, k, mask)
        total += batch
    est = float(hll_estimate(bundle.hll))
    err = abs(est - len(seen)) / max(len(seen), 1)
    return {"config": 2, "name": "tcpconnect-dns-hll-distinct",
            "metric": "hll_distinct_rel_error", "unit": "fraction",
            "value": round(err, 5),
            "extra": {"estimate": round(est, 1), "exact": len(seen),
                      "events": total}}


# ---------------------------------------------------------------------------
# config 3 — streaming top-k vs exact on a zipf stream
# ---------------------------------------------------------------------------

def config3_topk_vs_exact(seconds: float) -> dict:
    import jax.numpy as jnp

    from inspektor_gadget_tpu.ops import bundle_init
    from inspektor_gadget_tpu.ops.sketches import bundle_update_jit

    rng = np.random.default_rng(3)
    batch = 1 << 16
    # zipf over a 50k-file population — the top-file/block-io shape
    pop = 50_000
    ranks = np.arange(1, pop + 1, dtype=np.float64)
    probs = (1.0 / ranks ** 1.2)
    probs /= probs.sum()
    bundle = bundle_init()
    mask = jnp.ones(batch, dtype=bool)
    import jax
    warm = jnp.asarray(np.zeros(batch, np.uint32))
    jax.block_until_ready(
        bundle_update_jit(bundle_init(), warm, warm, warm, mask).events)
    exact: dict = {}
    deadline = time.monotonic() + seconds
    total = 0
    while time.monotonic() < deadline:
        keys = rng.choice(pop, size=batch, p=probs).astype(np.uint32) + 1
        _exact_update(exact, keys)
        k = jnp.asarray(keys)
        bundle = bundle_update_jit(bundle, k, k, k, mask)
        total += batch
    err = _hh_error(bundle, exact)
    return {"config": 3, "name": "topfile-blockio-topk-vs-exact",
            "metric": "heavy_hitter_error", "unit": "fraction",
            "value": round(err, 5),
            "extra": {"events": total, "population": pop}}


# ---------------------------------------------------------------------------
# config 4 — seccomp entropy + autoencoder anomaly scoring
# ---------------------------------------------------------------------------

def config4_seccomp_anomaly(seconds: float) -> dict:
    import jax
    import jax.numpy as jnp

    from inspektor_gadget_tpu.models.autoencoder import (
        AEConfig, ae_init, ae_score, ae_train_step, normalize_counts,
    )

    rng = np.random.default_rng(4)
    cfg = AEConfig(input_dim=512, hidden_dim=128, latent_dim=32)
    scorer = ae_init(cfg)
    # normal profile: zipf-shaped per-syscall rates (real workloads hammer
    # a few syscalls) — gives the AE structure a permutation can violate
    rates = 40.0 / np.arange(1, cfg.input_dim + 1, dtype=np.float64) ** 1.1
    base = rng.poisson(rates, (64, cfg.input_dim)).astype(np.float32)
    x = normalize_counts(jnp.asarray(base))
    for _ in range(200):  # brief online fit, as the advise path does
        scorer, _loss = ae_train_step(scorer, x)
    normal = np.asarray(ae_score(scorer, x))
    # anomalous profile: the same total mass spent on the WRONG syscalls
    perm = rng.permutation(cfg.input_dim)
    anom = np.asarray(ae_score(
        scorer, normalize_counts(jnp.asarray(base[:, perm]))))
    # scoring throughput
    score_jit = jax.jit(lambda p, v: ae_score(
        type(scorer)(params=p, opt_state=scorer.opt_state,
                     steps=scorer.steps, config=cfg), v))
    jax.block_until_ready(score_jit(scorer.params, x))
    n = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        jax.block_until_ready(score_jit(scorer.params, x))
        n += x.shape[0]
    rate = n / (time.perf_counter() - t0)
    sep = float(np.median(anom) / max(float(np.median(normal)), 1e-9))
    return {"config": 4, "name": "seccomp-entropy-ae-anomaly",
            "metric": "ae_scores_per_s", "unit": "containers/sec",
            "value": round(rate, 1),
            "extra": {"anomaly_separation_x": round(sep, 2),
                      "median_normal": round(float(np.median(normal)), 5),
                      "median_anomalous": round(float(np.median(anom)), 5)}}


# ---------------------------------------------------------------------------
# config 5 — multi-node merge at production shape + the concurrent
#            exec+tcp target workload
# ---------------------------------------------------------------------------

def config5_multinode_merge(seconds: float) -> dict:
    import jax
    import jax.numpy as jnp

    from inspektor_gadget_tpu.ops import bundle_init, bundle_merge

    devs = jax.devices()
    prod = dict(depth=4, log2_width=16, hll_p=14, entropy_log2_width=12,
                k=128)
    if len(devs) >= 2:
        # real mesh path: psum/pmax merge over the node axis
        from inspektor_gadget_tpu.models.autoencoder import AEConfig, ae_init
        from inspektor_gadget_tpu.parallel import (
            cluster_init, make_cluster_step, make_mesh,
        )
        n = len(devs)
        mesh = make_mesh(n_nodes=n, n_model=1)
        state = cluster_init(mesh, ae_init(AEConfig(
            input_dim=128, hidden_dim=64, latent_dim=16)), **prod)
        _step, merge = make_cluster_step(mesh, state)
        p50, p95 = _time_ticks(
            lambda: merge(state.bundle),
            lambda m: jax.block_until_ready(m.events))
        mode = f"psum-mesh-{n}dev"
    else:
        # single chip: the wire-plane pairwise merge at production shape
        a, b = bundle_init(**prod), bundle_init(**prod)
        merge_jit = jax.jit(bundle_merge)
        p50, p95 = _time_ticks(
            lambda: merge_jit(a, b),
            lambda m: jax.block_until_ready(m.events))
        mode = "pairwise-1dev"
    return {"config": 5, "name": "multinode-tcp-merge-production-shape",
            "metric": "merge_ms_p50", "unit": "ms",
            "value": p50,
            "extra": {"p95_ms": p95, "mode": mode, "shape": prod,
                      "target_ms": 50.0}}


def config5b_concurrent_exec_tcp(seconds: float) -> dict:
    """The stated target workload: `trace exec` + `trace tcp` streams
    ingested CONCURRENTLY through one sketch plane; reports combined
    throughput and heavy-hitter error vs exact counts."""
    import jax.numpy as jnp

    from inspektor_gadget_tpu.ops import bundle_init
    from inspektor_gadget_tpu.ops.sketches import bundle_update_jit
    from inspektor_gadget_tpu.sources import PySyntheticSource
    from inspektor_gadget_tpu.sources.bridge import (
        NativeCapture, SRC_SYNTH_EXEC, SRC_SYNTH_TCP, native_available,
    )

    batch = 1 << 16
    bundle = bundle_init()
    mask = jnp.ones(batch, dtype=bool)
    # compile outside the window — standalone runs must not pay the ~15s
    # first TPU compile inside the measured span
    import jax
    warm = jnp.asarray(np.zeros(batch, np.uint32))
    jax.block_until_ready(
        bundle_update_jit(bundle_init(), warm, warm, warm, mask).events)
    lock = threading.Lock()
    exact: dict = {}
    state = {"bundle": bundle, "events": 0}
    deadline = time.monotonic() + seconds

    def feed(kind_native, seed):
        nonlocal state
        if native_available():
            src = NativeCapture(kind_native, seed=seed, vocab=5000)
            folded = src.generate_folded
        else:
            py = PySyntheticSource(seed=seed, vocab=5000, batch_size=batch)
            from inspektor_gadget_tpu.ops import fold64_to_32

            def folded(n):
                return np.asarray(fold64_to_32(
                    py.generate(n).cols["key_hash"]))
        while time.monotonic() < deadline:
            keys = np.asarray(folded(batch), dtype=np.uint32)
            k = jnp.asarray(keys)
            with lock:  # one shared device bundle, two producers
                state["bundle"] = bundle_update_jit(
                    state["bundle"], k, k, k, mask)
                state["events"] += batch
                _exact_update(exact, keys)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=feed, args=(SRC_SYNTH_EXEC, 11)),
               threading.Thread(target=feed, args=(SRC_SYNTH_TCP, 22))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - t0
    err = _hh_error(state["bundle"], exact)
    return {"config": "5b", "name": "concurrent-exec-tcp-sketch-plane",
            "metric": "combined_ingest_ev_per_s", "unit": "events/sec",
            "value": round(state["events"] / max(elapsed, 1e-9), 1),
            "extra": {"heavy_hitter_error": round(err, 5),
                      "events": state["events"], "streams": 2,
                      "hh_target": 0.01}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=2.0,
                    help="measurement window per config")
    ap.add_argument("--configs", default="1,1d,2,3,4,5,5b")
    args = ap.parse_args(argv)
    import jax
    platform = jax.devices()[0].platform
    wanted = set(args.configs.split(","))
    # latency-sensitive merge timing runs FIRST: the ingest configs leave
    # producer threads draining for a moment after their window, and that
    # tail load inflates a subsequent merge-tick measurement ~1000x
    runners = [("5", config5_multinode_merge),
               ("2", config2_hll_distinct),
               ("3", config3_topk_vs_exact),
               ("4", config4_seccomp_anomaly),
               ("1", config1_trace_exec_runtime),
               ("1d", config1d_display_path),
               ("5b", config5b_concurrent_exec_tcp)]
    out = []
    failed = False
    for key, fn in runners:
        if key not in wanted:
            continue
        try:
            rec = fn(args.seconds)
        except DisplayPathRegression as e:
            # a tripped guardrail is a FAILURE of the run, not just a
            # record: the error is emitted AND the exit code goes nonzero
            rec = {"config": key, "error": str(e), "gate_failed": True}
            failed = True
        except Exception as e:  # noqa: BLE001 — a config must not kill the rest
            rec = {"config": key, "error": repr(e)}
        rec["platform"] = platform
        out.append(rec)
        time.sleep(0.5)  # let producer threads drain between configs
    for rec in sorted(out, key=lambda r: str(r["config"])):
        _emit(rec)
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
