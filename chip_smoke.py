"""chip_smoke.py — the quickest proof that the served sketch path still
starts on the chip.

    python chip_smoke.py [--seed N]            one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4             the sharded-ingest path, four chips
    python chip_smoke.py --platform cpu        tiny rehearsal for the tests
    python chip_smoke.py --only eventtime      one phase of the one-chip run

One process owns the chip for the whole run, so every phase runs in THIS
process — the agent phase serves in-process on a unix socket with its
client on another thread, and nothing here starts a child that needs the
device. Phases, one chip (each prints one JSON line; any failed check
raises, so the exit code is non-zero and no result line is printed):

  step-times     fused and scatter update step at the run's geometry and
                 at a narrow one where the kernel measured faster: which
                 arm the served path selects, first-call seconds, ms/step,
                 that both leave bit-identical state behind, and that the
                 selected arm is not the slower one by more than 10%
  local-runtime  `trace exec` through LocalRuntime.run_gadget: native
                 synthetic source -> pop -> operator chain -> tpusketch
                 (history on, one sealed window per 500 ms harvest), five
                 windows, then events/drops accounting and the harvest
                 against an exact dict/set reference of the same stream
  invertible     a short run with the invertible plane: decode == exact
  quantiles      a short run with the DDSketch plane: p50..p99.9 vs exact
  narrow         a short run at the narrow geometry: on a TPU the operator
                 takes the kernel there, and its arm counter says so
  anomaly        `advise seccomp-profile` with the anomaly scorer on, at
                 the sizes of chipbench/configs/seccomp-node.json: every
                 container's score against the plain replay of
                 chipbench/reference_scorer.py within its tolerance,
                 histograms and syscall sets exact; then the same run with
                 each of three faults planted in the scorer's step, which
                 must each read over the tolerance
  anomaly-dense  the same phase on chipbench/configs/dense-node.json's
                 stream (1,024 containers x 335 syscalls) with history on
                 at `history-max-slices 4096`: besides the above, every
                 sealed window's per-container slices against the exact
                 event counts of its batches, none dropped
  eventtime      `trace exec` on chipbench/configs/exec-eventtime.json's
                 stream (NEXmark's delayed events) with event-time windows
                 behind a watermark: a recorder keeps every event's (key,
                 ts), and every sealed window is held to
                 chipbench/reference_windows.py (exact count, none late,
                 aligned, every window sealed, top-k never under, HLL and
                 entropy within their envelopes); then the same run with a
                 window sealed early (no lateness in the watermark), which
                 must fail that comparison
  agent          one RunGadget from AgentClient against the agent service
                 (`agent.main serve`'s AgentServer) with --checkpoint-dir
                 semantics: the checkpointer thread reads device state
                 while the donating ingest step runs (native source; the
                 reference is a tap on what the agent's run loop was fed)

`--chips 4` runs ONLY the path across chips and what it is compared with:
the operator with shard-ingest over four lanes against the single-chip
fold of the same seeded stream — every harvested leaf equal, each lane's
state and staged batches on its own device, collectives in the harvest.

The last stdout line is
    {"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}
as JAX reports the device. Without an accelerator (or outside the repo)
the script fails before printing any result.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path

import numpy as np

# sizes: "tpu" is what users run (operator defaults: depth 4, width 2^16,
# hll-p 14, entropy 2^12, top-k 128; batch 65536; >= 10^5 zipf(1.2) keys);
# "cpu" is the tiny rehearsal the tests run — same phases, same checks
# "rate" is the native source's nominal rate: it paces in 1 ms ticks and
# never catches up, so it delivers well under it. 4M is meant to keep the
# update step (not the source) the limit of the ingest loop, and a whole
# run (five 500 ms windows, about 3 s) under the 2^24 events that the
# float32 counters count exactly (check_counters). "narrow" overrides the
# geometry with one where the fused kernel measured faster than the scatter
# composition (PERF.md, PR 26): there the served path takes the kernel.
SIZES = {
    "tpu": dict(batch=65536, vocab=131072, geometry={},
                narrow={"log2-width": "12", "hll-p": "12"}, windows=5,
                harvest="500ms", rate=4_000_000, deadline=420.0,
                time_steps=8, short_windows=2, shard_batches=10),
    "cpu": dict(batch=2048, vocab=3000,
                geometry={"depth": "2", "log2-width": "10", "hll-p": "8",
                          "entropy-log2-width": "8", "topk": "32",
                          "inv-log2-buckets": "10",
                          "history-log2-width": "8"},
                narrow={"hll-p": "10"}, windows=5, harvest="100ms",
                rate=400_000, deadline=120.0,
                time_steps=4, short_windows=2, shard_batches=10),
}
ZIPF = 1.2
# the anomaly phase: chipbench/configs/seccomp-node.json's stream (a key is
# a (container, syscall) pair: 64 x 335) and the operator's own scorer. A
# harvest every 100 ms keeps a run of `harvests` summaries, each a training
# step, to a few seconds: the third fault needs about thirty steps to drift
# past the tolerance. Scores are judged on the first `head` summaries and
# the last; the replay steps through every one
ANOMALY = dict(vocab=64 * 335, harvest="100ms", harvests=40, head=8)
# the same phase on chipbench/configs/dense-node.json's stream: upstream's
# cap of 1,024 containers x 335 syscalls, history on with room for a slice
# a container (2 x 1,024 + 1), a window a second; every sealed window's
# per-container slices are held to the exact event counts of its batches
DENSE = dict(containers=1024, vocab=1024 * 335, max_slices=4096,
             window="1s")
# the eventtime phase: chipbench/configs/exec-eventtime.json as it is on the
# chip; on the CPU its stream and windows at a tenth of the time (1 s
# windows, delays and lateness of 300 ms) at the rehearsal's geometry. A run
# ends after `seals` windows sealed on the watermark; the teardown seals the
# rest
EVENTTIME_CONFIG = (Path(__file__).resolve().parent / "chipbench" / "configs"
                    / "exec-eventtime.json")
EVENTTIME_CPU = {"history-interval": "1s", "history-lateness": "300ms",
                 "occasional-delay": "300ms"}
EVENTTIME_SEALS = 2
EVENTTIME_FAULTS = ("early", "processing")
INV_VOCAB = 500        # inside every decode capacity used here: complete
SHARD_VOCAB = 24       # < top-k on both sizes: the candidate table stays
#                        exact on every path (tests/test_sharded_ingest.py)


class SmokeFailure(AssertionError):
    """A phase check that did not hold."""


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(**fields) -> None:
    print(json.dumps(fields, default=float), flush=True)


def fold32(k64: np.ndarray) -> np.ndarray:
    """The 64->32 bit key fold, written out here: the reference stays
    independent of the code under test."""
    k = k64.astype(np.uint64, copy=False)
    return ((k >> np.uint64(32)) ^ (k & np.uint64(0xFFFFFFFF))).astype(
        np.uint32)


class ExactStream:
    """The plain reference: exact dict/set counts of the stream as the host
    hands it to the operator chain (and, optionally, one value column).
    observe() only keeps the folded keys — it runs inside the ingest loop
    and must not be what paces it; the counting happens after the run."""

    def __init__(self, value_col: str | None = None):
        self.keys: list[np.ndarray] = []
        self.events = 0
        self.drops = 0
        self.value_col = value_col
        self.values: list[np.ndarray] = []
        self._counts: dict[int, int] | None = None

    def observe(self, batch) -> None:
        n = batch.count
        self.keys.append(fold32(batch.cols["key_hash"][:n]))
        self.events += n
        self.drops = int(batch.drops)
        if self.value_col:
            self.values.append(batch.cols[self.value_col][:n].copy())

    @property
    def counts(self) -> dict[int, int]:
        if self._counts is None:
            u, c = np.unique(np.concatenate(self.keys), return_counts=True)
            self._counts = dict(zip(u.tolist(), c.tolist()))
        return self._counts

    def entropy_bits(self) -> float:
        c = np.fromiter(self.counts.values(), dtype=np.float64)
        p = c / c.sum()
        return float(-(p * np.log2(p)).sum())


class CompileClock:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events, since the last take()."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name: str, secs: float, **_kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def take(self) -> dict:
        out = {"compile_s": round(self.seconds, 2), "cache_hits": self.hits}
        self.seconds, self.hits = 0.0, 0
        return out


def metric(name: str) -> float:
    from inspektor_gadget_tpu.telemetry import snapshot
    return sum(v for k, v in snapshot().items()
               if k == name or k.startswith(name + "{"))


def arm_steps() -> dict[str, float]:
    """ig_tpusketch_update_arm_steps_total by its `arm` label."""
    from inspektor_gadget_tpu.telemetry import snapshot
    snap = snapshot()
    return {arm: sum(v for k, v in snap.items()
                     if k.startswith("ig_tpusketch_update_arm_steps_total{")
                     and f'arm="{arm}"' in k)
            for arm in ("fused", "scatter")}


def update_path(jitted, args: tuple) -> str:
    """Which update the step lowers to for these arguments: the fused
    kernel shows up in the lowered text as a tpu_custom_call carrying its
    name (the scatter path has one too on a TPU — the entropy histogram —
    so the name is the test, not the call)."""
    import jax

    from inspektor_gadget_tpu.ops.pallas_kernels import (FUSED_KERNEL_NAME,
                                                         kernel_in_lowered)
    avals = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=getattr(x, "sharding", None)),
        args)
    return ("fused" if kernel_in_lowered(jitted.lower(*avals).as_text(),
                                         FUSED_KERNEL_NAME) else "scatter")


def summary_dict(s) -> dict:
    """One shape for the in-process SketchSummary and the wire-decoded
    summary a client receives."""
    if isinstance(s, dict):
        return s
    return {"events": s.events, "drops": s.drops, "distinct": s.distinct,
            "entropy": s.entropy_bits, "heavy_hitters": s.heavy_hitters,
            "approx": s.approx, "accuracy": s.accuracy,
            "decoded": s.decoded, "inv": s.inv, "quantiles": s.quantiles}


def check_against_reference(s: dict, exact: ExactStream) -> dict:
    """Heavy hitters, distinct count and entropy of the final harvest
    against the exact reference, inside the envelope the operator itself
    reports (the summary's accuracy block)."""
    acc = (s.get("accuracy") or {}).get("stats")
    require(acc, "summary carries no accuracy block (audit-sample off?)")
    hh = [(int(k), int(c)) for k, c in s["heavy_hitters"] if c > 0]
    require(hh, "no heavy hitters harvested")
    bound_abs = float(acc["heavy_hitters"]["bound_abs"])
    over = [c - exact.counts.get(k, 0) for k, c in hh]
    require(min(over) >= 0,
            f"count-min UNDER-estimated a key by {-min(over)} — events "
            "were lost between the host block and the device")
    # the reported guarantee holds per key with the reported confidence
    allowed = math.ceil((1.0 - acc["heavy_hitters"]["confidence"]) * len(hh))
    beyond = sum(1 for o in over if o > bound_abs)
    require(beyond <= allowed,
            f"{beyond} of {len(hh)} heavy hitters overestimate by more "
            f"than the reported bound {bound_abs:.1f} (allowed {allowed})")
    # every key the bound guarantees a place in the table is there
    ranked = sorted(exact.counts.values(), reverse=True)
    k = len(s["heavy_hitters"])
    floor = (ranked[k] if len(ranked) > k else 0) + bound_abs
    must = {key for key, c in exact.counts.items() if c > floor}
    missing = must - {key for key, _ in hh}
    require(not missing, f"{len(missing)} guaranteed heavy keys missing")
    true_distinct = len(exact.counts)
    d_err = abs(float(s["distinct"]) - true_distinct) / true_distinct
    d_bound = 4.0 * float(acc["distinct"]["bound"])   # reported 1-sigma x 4
    require(d_err <= d_bound,
            f"distinct {s['distinct']:.0f} vs exact {true_distinct}: "
            f"{d_err:.4f} > {d_bound:.4f}")
    e_err = abs(float(s["entropy"]) - exact.entropy_bits())
    require(e_err <= float(acc["entropy"]["bound"]) + 1e-3,
            f"entropy off by {e_err:.3f} bits > reported bias bound "
            f"{acc['entropy']['bound']:.3f}")
    return {"hh_checked": len(hh), "hh_max_over": max(over),
            "hh_bound_abs": round(bound_abs, 1), "hh_guaranteed": len(must),
            "distinct": round(float(s["distinct"]), 1),
            "distinct_exact": true_distinct, "distinct_err": round(d_err, 5),
            "distinct_bound_4sigma": round(d_bound, 5),
            "entropy_err_bits": round(e_err, 4),
            "approx": bool(s.get("approx"))}


def sketch_settings(cfg: dict, extra: dict) -> dict[str, str]:
    """tpusketch on at the size's geometry (operator defaults on the chip);
    the audit sample makes every summary carry its accuracy block."""
    return {"enable": "true", "harvest-interval": cfg["harvest"],
            "audit-sample": "1024", **cfg["geometry"], **extra}


def bundle_geometry(cfg: dict) -> dict:
    """bundle_init keywords of the size's base bundle (the operator's
    parameter defaults where the size names none)."""
    g = cfg["geometry"]
    return dict(depth=int(g.get("depth", 4)),
                log2_width=int(g.get("log2-width", 16)),
                hll_p=int(g.get("hll-p", 14)),
                entropy_log2_width=int(g.get("entropy-log2-width", 12)),
                k=int(g.get("topk", 128)))


def sketch_params(cfg: dict, extra: dict):
    from inspektor_gadget_tpu.operators.operators import get as get_op
    tp = get_op("tpusketch").instance_params().to_params()
    for k, v in sketch_settings(cfg, extra).items():
        tp.set(k, v)
    return tp


def leaf_platforms(tree) -> set[str]:
    import jax
    return {d.platform for leaf in jax.tree.leaves(tree)
            for d in leaf.devices()}


# ---------------------------------------------------------------------------
# phase: step times (fused vs scatter at the run's geometry)
# ---------------------------------------------------------------------------

def time_arms(kw: dict, cfg: dict, platform: str, seed: int,
              clock: CompileClock) -> dict:
    """Both update arms as donating steps at the geometry `kw`: the arm
    the served step selects, each arm's first-call seconds and ms/step,
    and whether they left the same state behind."""
    import functools

    import jax
    import jax.numpy as jnp

    from inspektor_gadget_tpu.ops.sketches import (
        _bundle_update_pallas, bundle_ingest_jit, bundle_init, bundle_update,
        update_arm)
    from inspektor_gadget_tpu.sources.synthetic import PySyntheticSource

    n = cfg["batch"]
    src = PySyntheticSource(seed=seed, vocab=cfg["vocab"], zipf_s=ZIPF,
                            batch_size=n)
    keys = [jnp.asarray(fold32(src.generate(n).cols["key_hash"]))
            for _ in range(4)]
    w = jnp.ones(n, jnp.uint32)
    zero = jnp.float32(0)

    def with_token(update):
        def step(b, hh, di, ds, wt, drops):
            out = update(b, hh, di, ds, wt.astype(jnp.int32), drops)
            return out, out.events + 0.0
        return jax.jit(step, donate_argnums=0)

    # the production step is whatever bundle_update_fused dispatches to on
    # this backend; the other arm is built explicitly (the interpreter
    # stands in for the kernel off-TPU — named as such)
    args = (bundle_init(**kw), keys[0], keys[0], keys[0], w, zero)
    served = update_path(bundle_ingest_jit, args)
    selected = update_arm(args[0], n)
    require(selected == served,
            f"update_arm says {selected}, the step lowers to {served}")
    kernel_arm = "fused" if platform == "tpu" else "fused(interpret)"
    arms = ({"fused": bundle_ingest_jit, "scatter": with_token(bundle_update)}
            if served == "fused" else
            {kernel_arm: with_token(functools.partial(
                _bundle_update_pallas, interpret=platform != "tpu")),
             "scatter": bundle_ingest_jit})
    out: dict = {}
    final = {}
    for name, step in arms.items():
        b = bundle_init(**kw)
        t0 = time.perf_counter()
        b, tok = step(b, keys[0], keys[0], keys[0], w, zero)
        tok.block_until_ready()
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        for i in range(cfg["time_steps"]):
            k = keys[(i + 1) % 4]
            b, tok = step(b, k, k, k, w, zero)
        tok.block_until_ready()
        ms = (time.perf_counter() - t0) / cfg["time_steps"] * 1e3
        require(leaf_platforms(b) == {platform},
                f"{name} step output on {leaf_platforms(b)}")
        final[name] = jax.tree.map(np.asarray, b)
        out[name] = {"first_call_s": round(first, 2), "ms_per_step": ms,
                     **clock.take()}
    a, b = final.values()
    same = all(np.array_equal(x, y) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    require(same, "fused and scatter steps left different state behind")
    (other,) = [name for name in out if name != served]
    require(out[served]["ms_per_step"] <= 1.1 * out[other]["ms_per_step"],
            f"the served path selects {served} at {kw}: "
            f"{out[served]['ms_per_step']:.2f} ms/step against {other}'s "
            f"{out[other]['ms_per_step']:.2f}")
    return dict(geometry=kw, served_path=served, fused_equals_scatter=same,
                **out)


def phase_step_times(cfg: dict, platform: str, seed: int,
                     clock: CompileClock) -> None:
    narrow = dict(cfg, geometry={**cfg["geometry"], **cfg["narrow"]})
    say(phase="step-times", batch=cfg["batch"], steps_timed=cfg["time_steps"],
        **time_arms(bundle_geometry(cfg), cfg, platform, seed, clock),
        narrow=time_arms(bundle_geometry(narrow), cfg, platform, seed,
                         clock))


# ---------------------------------------------------------------------------
# phases through LocalRuntime.run_gadget
# ---------------------------------------------------------------------------

def run_local(cfg: dict, platform: str, seed: int, *, windows: int,
              sketch_extra: dict, vocab: int, value_col: str | None = None,
              history: bool = False):
    """`trace exec` on the native synthetic source with tpusketch enabled,
    twice: a one-window warm-up that compiles every program this
    configuration needs (the source keeps producing while a step compiles,
    and a compile stall inside the checked run would be nothing but
    drops), then the checked run. Returns the checked run's (final summary
    dict, exact reference, facts)."""
    for n in (1, windows):
        tmp = tempfile.mkdtemp(prefix="ig-smoke-hist-") if history else None
        try:
            out = _local_once(cfg, platform, seed, n, sketch_extra, vocab,
                              value_col, tmp)
        finally:
            if tmp:
                from inspektor_gadget_tpu.history import HISTORY
                HISTORY.close_all()
                shutil.rmtree(tmp, ignore_errors=True)
    return out


def check_counters(s: dict, exact: ExactStream) -> None:
    """Absorbed on the device == handed over by the host, drops included,
    EXACTLY. The bundle's events/drops counters are float32 scalars, exact
    only below 2^24 (ROADMAP D11), so a run that offers more than that
    cannot be checked and fails here instead of passing approximately."""
    offered = exact.events + exact.drops
    require(offered < 1 << 24,
            f"{offered} events offered: past 2^24 the float32 counters "
            "round, and this check needs them exact — lower the rate")
    require(s["events"] == exact.events,
            f"device absorbed {s['events']} events, host handed over "
            f"{exact.events}")
    require(s["drops"] == exact.drops,
            f"device counted {s['drops']} drops, source {exact.drops}")


def device_facts(view: dict) -> dict:
    """Where the state lives and which update the step lowers to, from
    the operator's own read-only view (TpuSketchInstance.device_view).
    Lowering traces the whole step: call it after the run, not inside."""
    return {"state_on": sorted({d.platform for shards in view["state_shards"]
                                for d, _shape in shards}),
            "path": update_path(*view["step"])}


def _local_once(cfg, platform, seed, windows, sketch_extra, vocab,
                value_col, history_dir):
    import inspektor_gadget_tpu.all_gadgets  # noqa: F401
    from inspektor_gadget_tpu.gadgets import GadgetContext, get
    from inspektor_gadget_tpu.operators import tpusketch
    from inspektor_gadget_tpu.params import Collection
    from inspektor_gadget_tpu.runtime import LocalRuntime

    desc = get("trace", "exec")
    params = desc.params().to_params()
    for k, v in {"source": "synthetic", "rate": str(cfg["rate"]),
                 "batch-size": str(cfg["batch"]), "vocab": str(vocab),
                 "zipf": str(ZIPF), "seed": str(seed)}.items():
        params.set(k, v)
    extra = dict(sketch_extra)
    if history_dir:
        extra.update({"history": "true", "history-interval": "0",
                      "history-dir": history_dir})
    op_params = Collection()
    op_params["operator.tpusketch."] = sketch_params(cfg, extra)

    exact = ExactStream(value_col)
    summaries: list = []
    sealed: list[dict] = []
    views: list[dict] = []
    steps0 = metric("ig_tpusketch_steps_total")
    arms0 = arm_steps()
    seal_fail0 = metric("ig_history_drops_total")

    def on_summary(s) -> None:
        summaries.append(s)
        if not views:
            (inst,) = tpusketch.live_instances()
            views.append(inst.device_view())
        if len(summaries) >= windows:
            ctx.cancel()

    ctx = GadgetContext(desc, gadget_params=params,
                        operator_params=op_params, timeout=cfg["deadline"],
                        extra={"on_sketch_summary": on_summary,
                               "on_window_sealed": sealed.append})
    t0 = time.perf_counter()
    result = LocalRuntime().run_gadget(ctx, on_batch=exact.observe)
    seconds = time.perf_counter() - t0
    require(not result.errors(), f"gadget run failed: {result.errors()}")
    require(len(summaries) >= windows + 1,
            f"only {len(summaries)} harvests (wanted {windows} windows and "
            f"the teardown harvest) inside {cfg['deadline']}s")
    s = summary_dict(summaries[-1])
    check_counters(s, exact)
    facts = device_facts(views[0])
    require(facts["state_on"] == [platform],
            f"sketch state lives on {facts['state_on']}, not {platform}")
    steps = int(metric("ig_tpusketch_steps_total") - steps0)
    by_arm = {arm: int(v - arms0[arm])
              for arm, v in arm_steps().items() if v > arms0[arm]}
    require(by_arm == {facts["path"]: steps},
            f"arm counter {by_arm}: the step lowers to {facts['path']} "
            f"and ran {steps} times")
    require(summaries[-1].pipeline["update_arm"] == facts["path"],
            f"summary names {summaries[-1].pipeline['update_arm']}, the "
            f"step lowers to {facts['path']}")
    facts.update(events_offered=exact.events + exact.drops,
                 events_absorbed=s["events"], drops=s["drops"],
                 steps=steps, arm_steps=by_arm,
                 harvests=len(summaries), run_s=round(seconds, 2),
                 generator="native C++ synthetic")
    if history_dir:
        require(metric("ig_history_drops_total") == seal_fail0,
                "a window seal failed")
        require(len(sealed) >= windows,
                f"{len(sealed)} windows sealed, wanted {windows}")
        require(sum(w["events"] for w in sealed) == exact.events,
                "sealed windows do not add up to the absorbed events")
        facts["windows_sealed"] = len(sealed)
    return s, exact, facts


def phase_local_runtime(cfg, platform, seed, clock) -> None:
    s, exact, facts = run_local(
        cfg, platform, seed, windows=cfg["windows"], sketch_extra={},
        vocab=cfg["vocab"], history=True)
    require(len(exact.counts) >= min(cfg["vocab"] // 4, 10 ** 5 // 4),
            f"only {len(exact.counts)} distinct keys in the stream")
    say(phase="local-runtime", **facts, **clock.take(),
        reference=check_against_reference(s, exact))


def phase_invertible(cfg, platform, seed, clock) -> None:
    s, exact, facts = run_local(
        cfg, platform, seed + 1, windows=cfg["short_windows"],
        sketch_extra={"invertible": "true"}, vocab=INV_VOCAB)
    require(s["inv"] and s["inv"]["complete"],
            f"invertible decode incomplete: {s['inv']}")
    require(dict(s["decoded"]) == exact.counts,
            "decoded (key, count) pairs differ from the exact reference")
    say(phase="invertible", **facts, **clock.take(),
        decoded_keys=len(s["decoded"]), decode_complete=True,
        decoded_equals_exact=True,
        reference=check_against_reference(s, exact))


def phase_quantiles(cfg, platform, seed, clock) -> None:
    s, exact, facts = run_local(
        cfg, platform, seed + 2, windows=cfg["short_windows"],
        sketch_extra={"quantiles": "true", "quantile-field": "pid"},
        vocab=cfg["vocab"], value_col="pid")
    q = s["quantiles"]
    require(q and q["total"] == exact.events,
            f"quantile plane counted {q and q['total']} of "
            f"{exact.events} events")
    vals = np.sort(np.concatenate(exact.values))
    errs = {}
    for name, rank in (("p50", .5), ("p90", .9), ("p99", .99),
                       ("p999", .999)):
        true = float(vals[int(rank * (vals.size - 1))])
        errs[name] = abs(q[name] - true) / true
        require(errs[name] <= q["alpha"] * 1.01,
                f"{name}={q[name]:.1f} vs exact {true:.1f}: relative error "
                f"{errs[name]:.4f} > alpha {q['alpha']}")
    say(phase="quantiles", **facts, **clock.take(), alpha=q["alpha"],
        rel_err={k: round(v, 5) for k, v in errs.items()},
        reference=check_against_reference(s, exact))


def phase_narrow(cfg, platform, seed, clock) -> None:
    s, exact, facts = run_local(
        cfg, platform, seed + 3, windows=cfg["short_windows"],
        sketch_extra=cfg["narrow"], vocab=cfg["vocab"])
    want = "fused" if platform == "tpu" else "scatter"
    require(facts["path"] == want,
            f"the operator took {facts['path']} at {cfg['narrow']}, "
            f"expected {want}")
    say(phase="narrow", geometry=cfg["narrow"], **facts, **clock.take(),
        reference=check_against_reference(s, exact))


# ---------------------------------------------------------------------------
# phase: the anomaly scorer through LocalRuntime.run_gadget
# ---------------------------------------------------------------------------

SCORER_FAULTS = ("skipped", "before", "bf16")


@contextlib.contextmanager
def scorer_fault(name: str):
    """One of three faults planted in the scorer's step for the runs made
    inside (the operator binds `tpusketch.anomaly_step` when an instance is
    made): `skipped` leaves the third harvest's training step out,
    `before` returns the scores of the weights the step started from,
    `bf16` rounds the parameters to bfloat16 after every step. "" plants
    nothing."""
    if not name:
        yield
        return
    import jax
    import jax.numpy as jnp
    from inspektor_gadget_tpu.models import autoencoder as ae
    from inspektor_gadget_tpu.operators import tpusketch

    real = tpusketch.anomaly_step
    score = jax.jit(lambda sc, c: ae.ae_score(sc, ae.normalize_counts(c)))

    def skipped(scorer, counts, mask):
        if int(scorer.steps) == 2:      # each scorer's third step
            return scorer.replace(steps=scorer.steps + 1), score(scorer,
                                                                 counts)
        return real(scorer, counts, mask)

    def before(scorer, counts, mask):
        old = jax.block_until_ready(score(scorer, counts))
        return real(scorer, counts, mask)[0], old

    def bf16(scorer, counts, mask):
        scorer, _scores = real(scorer, counts, mask)
        scorer = scorer.replace(params=jax.tree.map(
            lambda a: a.astype(jnp.bfloat16).astype(jnp.float32),
            scorer.params))
        return scorer, score(scorer, counts)

    tpusketch.anomaly_step = {"skipped": skipped, "before": before,
                              "bf16": bf16}[name]
    try:
        yield
    finally:
        tpusketch.anomaly_step = real


def reference_scorer():
    """chipbench/reference_scorer.py: the benchmark's directory is no
    package, so it is imported the way chipbench/run.py imports its own."""
    bench = str(Path(__file__).resolve().parent / "chipbench")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import reference_scorer as ref
    return ref


def slices_against_stream(windows: list, mntns: list[np.ndarray]) -> dict:
    """Sealed windows (in order, each whole batches of `mntns`, the run's
    batches) held to the exact per-container event counts: every container
    a window absorbed has its `mntns:<ns>` slice with exactly its events,
    no other container has one, and nothing was dropped."""
    at, exact, dropped = 0, True, 0
    for win in windows:
        events, held = 0, []
        while events < win.events and at < len(mntns):
            held.append(mntns[at])
            events += len(mntns[at])
            at += 1
        ids, counts = np.unique(np.concatenate(held), return_counts=True)
        got = {k: s["events"] for k, s in win.slices.items()
               if k.startswith("mntns:") and "|" not in k}
        exact &= events == win.events and got == {
            f"mntns:{ns}": c for ns, c in zip(ids.tolist(), counts.tolist())}
        dropped += win.slices_dropped
    return {"slices_exact": bool(exact), "slices_dropped": dropped,
            "windows": len(windows)}


def anomaly_run(cfg: dict, seed: int, fault: str = "",
                extra: dict | None = None, dense: bool = False) -> dict:
    """`advise seccomp-profile` on the native synthetic source with
    tpusketch and its anomaly scorer on, until `ANOMALY["harvests"]`
    summaries came, recorded by chipbench/reference_scorer.py's Recorder
    and held to its three answers. `dense` runs DENSE's stream with
    history on and holds the sealed windows' per-container slices to the
    stream too. Returns the readings, the last summary's `pipeline` block
    and the emitted profile."""
    import jax
    import inspektor_gadget_tpu.all_gadgets  # noqa: F401
    from inspektor_gadget_tpu.gadgets import GadgetContext, get
    from inspektor_gadget_tpu.models.autoencoder import AEConfig, ae_init
    from inspektor_gadget_tpu.operators import tpusketch
    from inspektor_gadget_tpu.params import Collection
    from inspektor_gadget_tpu.runtime import LocalRuntime

    ref = reference_scorer()

    desc = get("advise", "seccomp-profile")
    params = desc.params().to_params()
    stream = ({"containers": str(DENSE["containers"]),
               "vocab": str(DENSE["vocab"])} if dense
              else {"vocab": str(ANOMALY["vocab"])})
    for k, v in {"source": "synthetic", "rate": str(cfg["rate"]),
                 "batch-size": str(cfg["batch"]), **stream,
                 "zipf": str(ZIPF), "seed": str(seed)}.items():
        params.set(k, v)
    history_dir = tempfile.mkdtemp(prefix="ig-smoke-dense-") if dense else ""
    history = ({"history": "true", "history-interval": DENSE["window"],
                "history-max-slices": str(DENSE["max_slices"]),
                "history-dir": history_dir} if dense else {})
    op_params = Collection()
    op_params["operator.tpusketch."] = sketch_params(
        {**cfg, "harvest": ANOMALY["harvest"]},
        {"anomaly": "true", "audit-sample": "0", **history,
         **(extra or {})})
    rec = ref.Recorder()
    last: dict = {}

    def on_summary(s) -> None:
        rec.on_summary(s)
        (inst,) = tpusketch.live_instances()
        last.update(counts=inst.container_distributions(),
                    profile=inst.gadget.syscall_sets(), pipeline=s.pipeline)
        if len(rec.summaries) >= ANOMALY["harvests"]:
            ctx.cancel()

    ctx = GadgetContext(desc, gadget_params=params,
                        operator_params=op_params, timeout=cfg["deadline"],
                        extra={"on_sketch_summary": on_summary})
    from inspektor_gadget_tpu.history import HISTORY, decode_window
    windows = []
    try:
        with scorer_fault(fault):
            result = LocalRuntime().run_gadget(ctx, on_batch=rec.on_batch)
        if dense:
            windows = [decode_window(h, p) for h, p in HISTORY.fetch_windows(
                base_dir=history_dir, gadget=desc.full_name)]
    finally:
        if dense:
            HISTORY.close_all()
            shutil.rmtree(history_dir, ignore_errors=True)
    require(not result.errors(), f"gadget run failed: {result.errors()}")
    require(len(rec.summaries) > ANOMALY["harvests"],
            f"only {len(rec.summaries)} harvests inside {cfg['deadline']}s")
    dim = 1 << int(cfg["geometry"].get("entropy-log2-width", 12))
    # the weights the operator starts from (ae_init is seeded), as arrays
    start = jax.tree.map(np.asarray, ae_init(AEConfig(
        input_dim=dim, hidden_dim=256, latent_dim=64)).params)
    judged = list(range(ANOMALY["head"])) + [len(rec.summaries) - 1]
    readings = ref.compare(rec, start, dim, profile=last["profile"],
                           counts=last["counts"], summaries=judged)
    if dense:
        readings.update(slices_against_stream(windows, rec.mntns))
    return {"readings": readings, "tolerance": ref.TOLERANCE,
            "windows": windows,
            "pipeline": last["pipeline"], "emitted": result.first(),
            "recorded": rec, "events": sum(len(a) for a in rec.mntns)}


def phase_anomaly(cfg, platform, seed, clock, dense: bool = False) -> None:
    sound = anomaly_run(cfg, seed + 4, dense=dense)
    r, tol = sound["readings"], sound["tolerance"]
    if dense:
        require(r["windows"] >= 2, f"only {r['windows']} windows sealed")
        require(r["slices_exact"], "a sealed window's per-container slices "
                "differ from the exact event counts of its batches")
        require(r["slices_dropped"] == 0,
                f"{r['slices_dropped']} slices dropped under the cap")
    require(r["score_keys_equal"], "a summary's scores name other "
            "containers than the stream held")
    require(r["score_gap"] <= tol,
            f"scores off the reference's replay by {r['score_gap']:.4f} "
            f"> {tol}")
    require(r["histograms_exact"], "per-container histograms differ from "
            "the exact counts of the stream")
    require(r["profile_exact"], "recorded syscall sets differ from the "
            "stream's")
    block = sound["pipeline"]["anomaly"]
    require(block["steps"] == r["harvests"],
            f"{block['steps']} scorer steps for {r['harvests']} harvests")
    faults = {}
    for i, fault in enumerate(SCORER_FAULTS):
        gap = anomaly_run(cfg, seed + 5 + i, fault,
                          dense=dense)["readings"]["score_gap"]
        require(gap > tol, f"the planted fault {fault!r} reads {gap:.4f}, "
                f"inside the tolerance {tol}: the comparison is blind to it")
        faults[fault] = gap
    stages = sound["pipeline"]["turn"]
    say(phase="anomaly-dense" if dense else "anomaly",
        events=sound["events"], **r, tolerance=tol,
        **block, fault_gaps=faults,
        anomaly_score_ms_per_harvest=round(
            1e3 * stages["anomaly_score_s"] / block["steps"], 3),
        record_ms_per_turn=round(
            1e3 * stages["stages"]["gadget_record"] / stages["turns"], 3),
        dists_ms_per_turn=round(
            1e3 * stages["stages"]["tpusketch_container_dists"]
            / stages["turns"], 3), **clock.take())


# ---------------------------------------------------------------------------
# phase: event-time windows against the plain reference of every event
# ---------------------------------------------------------------------------

def reference_windows():
    """chipbench/reference_windows.py, imported as reference_scorer() is."""
    reference_scorer()
    import reference_windows as ref
    return ref


@contextlib.contextmanager
def window_fault(name: str):
    """A fault planted in the operator's event-time windows: `early` seals
    a window once the newest event time passes its end (the watermark
    without its lateness), `processing` routes every event of a batch into
    the newest open window, as the processing clock would."""
    from inspektor_gadget_tpu.operators import tpusketch
    cls = tpusketch.TpuSketchInstance
    real = {"early": cls._watermark_ns, "processing": cls._route}.get(name)

    def early(self):
        return real(self) + self._late_ns

    def processing(self, ts, rows, span):
        age, _groups = real(self, ts, rows, span)
        age[:len(ts)] = 0
        self._win_events[self._ev_hi] = (
            self._win_events.get(self._ev_hi, 0) + len(ts))
        return age, [(self._ev_hi, None)]

    if name:
        setattr(cls, {"early": "_watermark_ns",
                      "processing": "_route"}[name], locals()[name])
    try:
        yield
    finally:
        if name:
            setattr(cls, {"early": "_watermark_ns",
                          "processing": "_route"}[name], real)


def eventtime_settings(cfg: dict, platform: str, **source) -> tuple:
    """(gadget parameters, operator parameters, window seconds) of the
    eventtime phase: the configuration's own on a TPU, at the rehearsal's
    sizes and a tenth of the time on the CPU."""
    from inspektor_gadget_tpu.params.params import parse_duration
    config = json.loads(EVENTTIME_CONFIG.read_text())
    gadget = {**config["gadget_params"], "rate": str(cfg["rate"])}
    operator = dict(config["operator"])
    if platform == "cpu":
        gadget.update({"batch-size": str(cfg["batch"]),
                       "vocab": str(cfg["vocab"]),
                       "occasional-delay": EVENTTIME_CPU["occasional-delay"]})
        operator.update({**cfg["geometry"],
                         "harvest-interval": cfg["harvest"],
                         "history-interval": EVENTTIME_CPU["history-interval"],
                         "history-lateness": EVENTTIME_CPU["history-lateness"]})
    gadget.update(source)
    return (gadget, operator, parse_duration(operator["history-interval"]),
            config["limits"])


def eventtime_run(cfg: dict, platform: str, seed: int, fault: str = "",
                  seals: int = EVENTTIME_SEALS, behind_s: float = 0.0,
                  **source) -> dict:
    """`trace exec` with event-time windows until `seals` windows sealed on
    the watermark, every event's folded key and ts recorded, and the
    sealed windows (the teardown's too) read back from the store and held
    to chipbench/reference_windows.py. `source` overrides gadget
    parameters (a delay past the lateness: late events expected);
    `behind_s` sets the operator's clock that far ahead of the events'
    own, as a source that runs behind the clock sees it."""
    import inspektor_gadget_tpu.all_gadgets  # noqa: F401
    from inspektor_gadget_tpu.gadgets import GadgetContext, get
    from inspektor_gadget_tpu.history import HISTORY, decode_window
    from inspektor_gadget_tpu.params import Collection
    from inspektor_gadget_tpu.params.params import parse_duration
    from inspektor_gadget_tpu.runtime import LocalRuntime

    gadget, operator, width, limits = eventtime_settings(cfg, platform,
                                                         **source)
    desc = get("trace", "exec")
    params = desc.params().to_params()
    for k, v in {**gadget, "seed": str(seed)}.items():
        params.set(k, str(v))
    history_dir = tempfile.mkdtemp(prefix="ig-smoke-eventtime-")
    op_params = Collection()
    op_params["operator.tpusketch."] = sketch_params(
        {**cfg, "harvest": operator["harvest-interval"]},
        {**operator, "audit-sample": "0", "history-dir": history_dir})
    keys: list[np.ndarray] = []
    stamps: list[np.ndarray] = []
    sealed: list[dict] = []
    pipeline: dict = {}

    def on_batch(batch) -> None:
        n = batch.count
        keys.append(fold32(batch.cols["key_hash"][:n]))
        stamps.append(batch.cols["ts"][:n].copy())

    def on_sealed(header: dict) -> None:
        sealed.append(header)
        if len(sealed) >= seals:
            ctx.cancel()

    extra = {"on_window_sealed": on_sealed,
             "on_sketch_summary": lambda s: pipeline.update(s.pipeline)}
    if behind_s:
        extra["history_clock"] = lambda: time.time() + behind_s
    ctx = GadgetContext(desc, gadget_params=params, operator_params=op_params,
                        timeout=cfg["deadline"], extra=extra)
    try:
        with window_fault(fault):
            result = LocalRuntime().run_gadget(ctx, on_batch=on_batch)
        windows = [decode_window(h, p) for h, p in HISTORY.fetch_windows(
            base_dir=history_dir, gadget=desc.full_name)]
    finally:
        HISTORY.close_all()
        shutil.rmtree(history_dir, ignore_errors=True)
    require(not result.errors(), f"gadget run failed: {result.errors()}")
    keys_all, ts_all = np.concatenate(keys), np.concatenate(stamps)
    geometry = {k: int(operator.get(k, d)) for k, d in (
        ("hll-p", 14), ("entropy-log2-width", 12))}
    delay = parse_duration(gadget.get("occasional-delay", "0s"))
    late_expected = delay > parse_duration(operator["history-lateness"])
    ok, rows = reference_windows().compare(
        windows, keys_all, ts_all, width_s=width, geometry=geometry,
        limits=limits, expect_late=late_expected)
    return {"ok": ok, "compared": rows, "windows": windows,
            "events": len(keys_all), "width_s": width,
            "announced": sealed, "keys": keys_all, "ts": ts_all,
            "pipeline": pipeline}


def phase_eventtime(cfg, platform, seed, clock) -> None:
    sound = eventtime_run(cfg, platform, seed + 20)
    rows = sound["compared"]
    require(sound["ok"], "sealed event-time windows differ from the plain "
            f"reference: {rows}")
    early = eventtime_run(cfg, platform, seed + 21, fault="early", seals=1)
    require(not early["ok"], "a window sealed before its late events came "
            f"passes the comparison: {early['compared']}")
    say(phase="eventtime", events=sound["events"],
        windows=len(sound["windows"]), width_s=sound["width_s"],
        compared={k: v["value"] for k, v in rows.items()},
        early_fault={k: v["value"] for k, v in early["compared"].items()
                     if v["limit"] is not None
                     and v["value"] > v["limit"]}, **clock.take())


# ---------------------------------------------------------------------------
# phase: through the agent (in-process service, client on its own thread)
# ---------------------------------------------------------------------------

def phase_agent(cfg, platform, seed, clock) -> None:
    from inspektor_gadget_tpu.agent.client import AgentClient
    from inspektor_gadget_tpu.agent.service import serve
    from inspektor_gadget_tpu.operators import tpusketch
    from inspektor_gadget_tpu.ops.sketches import bundle_init
    from inspektor_gadget_tpu.utils.checkpoint import load_pytree

    tmp = tempfile.mkdtemp(prefix="ig-smoke-agent-")
    addr = f"unix://{tmp}/agent.sock"
    run_params = {
        # the native source, as in the LocalRuntime phases: the numpy one
        # makes a batch in about 0.4 s, which leaves the chip idle and the
        # checkpointer alone with it
        "gadget.source": "synthetic", "gadget.rate": str(cfg["rate"]),
        "gadget.batch-size": str(cfg["batch"]),
        "gadget.vocab": str(cfg["vocab"]), "gadget.zipf": str(ZIPF),
        "gadget.seed": str(seed + 3),
        **{f"operator.tpusketch.{k}": v
           for k, v in sketch_settings(cfg, {}).items()}}
    ok0 = metric("ig_tpusketch_checkpoints_total")
    fail0 = metric("ig_tpusketch_checkpoint_failures_total")
    steps0 = metric("ig_tpusketch_steps_total")
    server, agent = serve(addr, node_name="smoke",
                          checkpoint_dir=f"{tmp}/ckpt",
                          checkpoint_interval=0.2)
    # the reference is what the agent's own run loop handed its operator
    # chain: a tap on the batch callback of the agent's runtime (the
    # service serves in this process, so its runtime is at hand)
    exact = ExactStream()
    run_gadget = agent.runtime.run_gadget

    def tapped(ctx, *, on_batch=None, **kw):
        def tee(batch) -> None:
            exact.observe(batch)
            on_batch(batch)
        return run_gadget(ctx, on_batch=tee, **kw)

    agent.runtime.run_gadget = tapped
    client = AgentClient(addr, "smoke")
    summaries: list[dict] = []
    stop = threading.Event()
    out: dict = {}
    views: list[dict] = []

    def on_summary(_node, s) -> None:
        summaries.append(s)
        if len(summaries) >= cfg["windows"]:
            stop.set()

    def drive() -> None:
        out.update(client.run_gadget(
            "trace", "exec", run_params, timeout=cfg["deadline"],
            outputs=("summary",), on_summary=on_summary, stop_event=stop))

    t = threading.Thread(target=drive, name="smoke-client")
    try:
        t0 = time.perf_counter()
        t.start()
        while t.is_alive():
            # a read of live device state from a third thread, beside
            # the ingest step and the checkpointer
            views += [i.device_view() for i in tpusketch.live_instances()]
            time.sleep(0.05)
        t.join()
        seconds = time.perf_counter() - t0
        saved = load_pytree(f"{tmp}/ckpt/trace-exec",
                            like=bundle_init(**bundle_geometry(cfg)))
    finally:
        stop.set()
        client.close()
        agent.stop_checkpointer()
        server.stop(grace=1.0).wait()
        tpusketch.set_checkpoint_dir(None)
        shutil.rmtree(tmp, ignore_errors=True)
    require(out.get("error") is None, f"RunGadget failed: {out.get('error')}")
    require(out["gaps"] == 0 and out["sub_drops"] == 0,
            f"summary stream lost records: {out}")
    require(len(summaries) >= cfg["windows"] + 1,
            f"only {len(summaries)} summaries reached the client")
    s = summaries[-1]
    check_counters(s, exact)
    require(views, "no live sketch instance was seen during the run")
    state_on = {d.platform for v in views for shards in v["state_shards"]
                for d, _shape in shards}
    require(state_on == {platform},
            f"agent's sketch state lives on {state_on}, not {platform}")
    saves = metric("ig_tpusketch_checkpoints_total") - ok0
    require(metric("ig_tpusketch_checkpoint_failures_total") == fail0,
            "a checkpoint failed while ingest was running")
    require(saves >= 2, f"only {saves:.0f} checkpoint(s) were taken")
    require(float(saved.events) == s["events"],
            f"final checkpoint holds {float(saved.events):.0f} events, the "
            f"client was told {s['events']}")
    steps = int(metric("ig_tpusketch_steps_total") - steps0)
    # the checkpointer has to meet a busy ingest loop for the donation
    # race to be exercised at all: a run the source starved proves little
    require(steps >= saves,
            f"{steps} steps against {saves:.0f} checkpoints: the source "
            "left the ingest loop idle")
    say(phase="agent", **device_facts(views[-1]), events_absorbed=s["events"],
        events_offered=exact.events + exact.drops, drops=s["drops"],
        steps=steps, summaries=len(summaries), checkpoints=int(saves),
        checkpoint_failures=0, steps_per_checkpoint=round(steps / saves, 2),
        run_s=round(seconds, 2), generator="native C++ synthetic",
        reference_from="tap on the agent runtime's batch callback",
        **clock.take(), reference=check_against_reference(s, exact))


# ---------------------------------------------------------------------------
# --chips N: the path across chips, through the operator
# ---------------------------------------------------------------------------

def phase_sharded(cfg, platform, seed, chips, clock) -> None:
    import jax

    import inspektor_gadget_tpu.all_gadgets  # noqa: F401
    from inspektor_gadget_tpu.gadgets import GadgetContext, get
    from inspektor_gadget_tpu.operators import tpusketch
    from inspektor_gadget_tpu.operators.operators import get as get_op
    from inspektor_gadget_tpu.ops.sketches import bundle_init
    from inspektor_gadget_tpu.sources.synthetic import PySyntheticSource
    from inspektor_gadget_tpu.utils.checkpoint import load_pytree

    def instance(extra: dict):
        ctx = GadgetContext(get("trace", "exec"))
        ctx.gadget_params.set("batch-size", str(cfg["batch"]))
        return get_op("tpusketch").instantiate(
            ctx, None, sketch_params(cfg, {"harvest-interval": "1h", **extra}))

    src = PySyntheticSource(seed=seed, vocab=SHARD_VOCAB, zipf_s=ZIPF,
                            batch_size=cfg["batch"])
    batches = [src.generate() for _ in range(cfg["shard_batches"])]
    mid = len(batches) // 2 + 1   # an open round at the mid-run harvest
    tmp = tempfile.mkdtemp(prefix="ig-smoke-shard-")

    def fold(inst, name: str, probe=None):
        """Feed the stream; harvest mid-run and at the end. Returns the
        two summaries and the harvested bundle as the instance
        checkpoints it (what every read path consumes), on the host."""
        seen = []
        for i, b in enumerate(batches):
            inst.enrich_batch(b)
            if probe:
                probe(inst.device_view())
            if i + 1 == mid:
                seen.append(inst.harvest())
        seen.append(inst.harvest())
        tpusketch.set_checkpoint_dir(f"{tmp}/{name}")
        try:
            inst.checkpoint()
        finally:
            tpusketch.set_checkpoint_dir(None)
        saved = load_pytree(f"{tmp}/{name}/trace-exec",
                            like=bundle_init(**bundle_geometry(cfg)))
        return seen, jax.tree.map(np.asarray, saved), inst.device_view()

    try:
        ref = instance({})
        want, want_bundle, ref_view = fold(ref, "single")
        ref_facts = device_facts(ref_view)
        require(ref_facts["state_on"] == [platform], "reference off-device")
        ref.post_gadget_run()
        single = clock.take()

        sh = instance({"shard-ingest": "true", "chips": str(chips)})
        staged_on: dict[int, str] = {}

        def probe(view: dict) -> None:
            # a staged batch is parked on its lane until the round
            # dispatches (the mid-run harvest leaves the later lanes an
            # open round too)
            for lane, homes in view["staged"].items():
                staged_on[lane] = str(homes[0])
                require(set(homes) == {view["lane_devices"][lane]},
                        f"lane {lane}'s staged batch sits on {homes}")

        got, got_bundle, view = fold(sh, "sharded", probe)
        sh.post_gadget_run()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    devices = view["lane_devices"]
    require(view["lanes"] == chips and len(set(devices)) == chips,
            f"sharded ingest not engaged over {chips} devices: {devices}")
    require(len(staged_on) == chips,
            f"staged batches seen on lanes {sorted(staged_on)} only")
    for shards in view["state_shards"]:
        require([d for d, _shape in shards] == devices,
                f"lane state on {shards}, wanted one lane per device")
        require(all(shape[0] == 1 for _d, shape in shards),
                "a device holds more than its own lane")
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_leaves_with_path(want_bundle)]
    diverged = [n for n, a, b in zip(names, jax.tree.leaves(want_bundle),
                                     jax.tree.leaves(got_bundle))
                if not np.array_equal(a, b)]
    require(not diverged, f"harvested leaves differ from the single-chip "
                          f"fold: {diverged}")
    for g, w in zip(got, want):
        require((g.events, g.drops, g.distinct, g.entropy_bits,
                 g.heavy_hitters) == (w.events, w.drops, w.distinct,
                                      w.entropy_bits, w.heavy_hitters),
                "sharded harvest summary differs from the single-chip one")
    harvest, harvest_args = view["harvest"]
    hlo = harvest.lower(*harvest_args).compile().as_text()
    collectives = sorted(c for c in ("all-reduce", "all-gather")
                         if c in hlo)
    require(collectives == ["all-gather", "all-reduce"],
            f"harvest compiled without its collectives: {collectives}")
    say(phase="sharded", chips=chips, batches=len(batches),
        events=got[-1].events, leaves_equal=len(names),
        lane_devices=[str(d) for d in devices],
        staged_on=[staged_on[k] for k in sorted(staged_on)],
        harvest_collectives=collectives,
        path_sharded=update_path(*view["step"]),
        path_single=ref_facts["path"], compile_single=single,
        compile_sharded=clock.take(),
        generator="pysynthetic (numpy)")


# ---------------------------------------------------------------------------

ONE_CHIP_PHASES = ("step-times", "local-runtime", "invertible", "quantiles",
                   "narrow", "anomaly", "anomaly-dense", "eventtime",
                   "agent")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="chip_smoke.py")
    ap.add_argument("--seed", type=int, default=20240921)
    ap.add_argument("--platform", default="tpu", choices=("tpu", "cpu"),
                    help="tpu (default): fail at once without one; cpu: "
                         "the tiny rehearsal the tests run")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run ONLY the sharded-ingest path across four "
                         "chips and its single-chip comparison")
    ap.add_argument("--only", action="append", default=[],
                    choices=ONE_CHIP_PHASES,
                    help="run only this phase of the one-chip run "
                         "(repeatable)")
    args = ap.parse_args(argv)

    import jax

    from inspektor_gadget_tpu.sources.bridge import NativeCapture
    from inspektor_gadget_tpu.utils.compile_cache import ensure_compile_cache
    from inspektor_gadget_tpu.utils.platform_probe import (
        PlatformUnavailable, acquire_platform)

    if args.platform == "cpu" and args.chips > 1:
        jax.config.update("jax_num_cpu_devices", args.chips)
    cache_dir = ensure_compile_cache()
    try:
        acq = acquire_platform(args.platform)
    except PlatformUnavailable as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 1
    require(acq["device_count"] >= args.chips,
            f"--chips {args.chips} on {acq['device_count']} device(s)")
    platform = acq["platform"]
    cfg = SIZES[platform]
    clock = CompileClock()
    t0 = time.perf_counter()
    say(phase="acquire", platform=platform, device_kind=acq["device_kind"],
        device_count=acq["device_count"], compile_cache=cache_dir,
        seed=args.seed, jax=jax.__version__)
    if args.chips > 1:
        phase_sharded(cfg, platform, args.seed, args.chips, clock)
    else:
        # the native source is part of the served path: building it (make
        # decides) must work here, loudly
        NativeCapture(1).close()
        for name in ONE_CHIP_PHASES:
            if args.only and name not in args.only:
                continue
            if name == "anomaly-dense":
                phase_anomaly(cfg, platform, args.seed + 10, clock,
                              dense=True)
            else:
                globals()["phase_" + name.replace("-", "_")](
                    cfg, platform, args.seed, clock)
    say(phase="done", seconds=round(time.perf_counter() - t0, 1))
    print(json.dumps({"ok": True, "device": {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
