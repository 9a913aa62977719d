"""Accuracy-audit-plane micro-bench → schema-valid PerfRecords.

ISSUE 19 satellite: the audit plane's cost model is one claim — the
shadow-sample feed rides an existing host lane, so turning the plane on
costs a bounded slice of ingest throughput, not a new pipeline stage.
Its value model is another — the observed heavy-hitter error the shadow
audit reports must actually sit well inside the CMS analytic bound at
the documented geometry. This bench measures both and publishes three
series to the perf ledger:

  * `accuracy-audit` / `audit_feed` (events/sec): ingest throughput
    (the jitted bundle update) WITH the bottom-k shadow sample folding
    every batch.
  * `accuracy-overhead` / `audit_overhead` (fraction, lower better):
    relative ingest throughput cost of the plane — the same loop with
    the feed off vs on.
  * `accuracy-observed-err` / `cms_observed_err` (pct, lower better):
    shadow-audited heavy-hitter relative error of a real CountMin at
    depth=4 / width=65536 over a millions-of-events zipf stream — the
    machine backing for the "well under the 1%" prose in
    ops/countmin.py (tools/check_perf_claims.py checks it against
    `extra.observed_err_pct`).

Run standalone (`python -m inspektor_gadget_tpu.perf.accuracy_bench
[--ledger PATH] [--batch N] [--capacity K] [--events N]`) or from tests
with tiny shapes; `bench compare` gates the series like any other.
"""

from __future__ import annotations

import argparse
import time

import numpy as np


def _zipf_keys(events: int, vocab: int = 4096, s: float = 1.2,
               seed: int = 42) -> np.ndarray:
    """Synthetic zipf-weighted uint32 key stream (1..vocab — key 0 is
    reserved as padding throughout the repo, so the stream avoids it)."""
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    p = ranks ** -s
    p /= p.sum()
    return (rng.choice(vocab, size=events, p=p) + 1).astype(np.uint32)


def measure_feed(*, batch: int = 1 << 14, capacity: int = 1024,
                 seconds: float = 0.5, vocab: int = 4096) -> dict:
    """Ingest throughput with vs without the shadow sample: the same
    jitted bundle update absorbs the same zipf batches, and the audited
    loop additionally folds every batch into the bottom-k sample (the
    operator's `audit-sample > 0` path). The overhead fraction is the
    throughput the plane actually costs a real ingest loop — not a
    micro number against a no-op baseline."""
    import jax
    import jax.numpy as jnp

    from ..ops.accuracy import ShadowSample
    from ..ops.sketches import bundle_init, bundle_update_jit

    keys = _zipf_keys(batch * 8, vocab=vocab)
    host_batches = [keys[i * batch:(i + 1) * batch] for i in range(8)]
    dev_batches = [jnp.asarray(b) for b in host_batches]
    mask = jnp.ones(batch, jnp.bool_)

    def loop(feed: bool) -> tuple[int, float]:
        bundle = bundle_init()
        sh = ShadowSample(capacity)
        bundle = bundle_update_jit(bundle, dev_batches[0], dev_batches[0],
                                   dev_batches[0], mask)
        jax.block_until_ready(bundle.events)  # compile outside the window
        sh.update(host_batches[0])  # warm: fill the reservoir once
        steps = 0
        t0 = time.perf_counter()
        while True:
            i = steps % 8
            bundle = bundle_update_jit(bundle, dev_batches[i],
                                       dev_batches[i], dev_batches[i], mask)
            if feed:
                sh.update(host_batches[i])
            steps += 1
            if steps % 8 == 0:
                jax.block_until_ready(bundle.events)
                if time.perf_counter() - t0 >= seconds:
                    break
        jax.block_until_ready(bundle.events)
        return steps, max(time.perf_counter() - t0, 1e-9)

    base_steps, base_s = loop(False)
    fed_steps, fed_s = loop(True)
    base_ev = base_steps * batch / base_s
    fed_ev = fed_steps * batch / fed_s
    return {
        "batch": batch, "capacity": capacity, "vocab": vocab,
        "steps": fed_steps, "events": fed_steps * batch, "seconds": fed_s,
        "base_ev_per_s": base_ev, "ev_per_s": fed_ev,
        "audit_overhead": max(1.0 - fed_ev / max(base_ev, 1e-9), 0.0),
    }


def measure_observed_err(*, events: int = 2_000_000, batch: int = 1 << 16,
                         vocab: int = 4096, capacity: int = 1024,
                         depth: int = 4, log2_width: int = 16,
                         top: int = 32) -> dict:
    """Shadow-audited observed error of a REAL CountMin at the geometry
    ops/countmin.py documents: feed a zipf stream to the sketch and the
    bottom-k shadow sample side by side, take the audited heavy keys'
    exact counts from the full stream, and report the mean relative
    overestimate of the sketch's point queries — next to the analytic
    e/width bound the docs quote."""
    import jax.numpy as jnp

    from ..ops.accuracy import ShadowSample, cms_bound
    from ..ops.countmin import cms_init, cms_query, cms_update

    keys = _zipf_keys(events, vocab=vocab, seed=7)
    cms = cms_init(depth=depth, log2_width=log2_width)
    sh = ShadowSample(capacity)
    for i in range(0, events, batch):
        chunk = keys[i:i + batch]
        cms = cms_update(cms, jnp.asarray(chunk))
        sh.update(chunk)
    exact = np.bincount(keys.astype(np.int64), minlength=vocab + 1)
    # audit set: the shadow-resident keys, heaviest first — the same
    # ground-truth set the operator's accuracy block audits against
    resident = sh.keys[np.argsort(-exact[sh.keys.astype(np.int64)])]
    audited = resident[:top].astype(np.int64)
    est = np.asarray(cms_query(cms, jnp.asarray(audited.astype(np.uint32))),
                     dtype=np.float64)
    truth = exact[audited].astype(np.float64)
    rel = (est - truth) / np.maximum(truth, 1.0)
    bound = cms_bound(depth, 1 << log2_width, float(events))
    return {
        "events": events, "vocab": vocab, "depth": depth,
        "log2_width": log2_width, "capacity": capacity,
        "audited_keys": int(audited.size),
        "observed_err_pct": float(np.mean(rel)) * 100.0,
        "max_err_pct": float(np.max(rel)) * 100.0,
        "bound_pct": float(bound["bound"]) * 100.0,
    }


def feed_record(stats: dict, provenance: dict) -> dict:
    from .schema import make_record
    return make_record(
        config="accuracy-audit", metric="audit_feed", unit="events/sec",
        value=stats["ev_per_s"],
        stages={"audit_feed": {"seconds": stats["seconds"],
                               "events": float(stats["events"]),
                               "ev_per_s": stats["ev_per_s"],
                               "calls": float(stats["steps"])}},
        provenance=provenance,
        extra={"batch": stats["batch"], "capacity": stats["capacity"],
               "vocab": stats["vocab"],
               "audit_overhead": round(stats["audit_overhead"], 4)})


def overhead_record(stats: dict, provenance: dict) -> dict:
    from .schema import make_record
    return make_record(
        config="accuracy-overhead", metric="audit_overhead",
        unit="fraction", value=round(stats["audit_overhead"], 4),
        stages={"audit_feed": {"seconds": stats["seconds"],
                               "ev_per_s": stats["ev_per_s"],
                               "calls": float(stats["steps"])}},
        provenance=provenance,
        extra={"batch": stats["batch"], "capacity": stats["capacity"],
               "base_ev_per_s": round(stats["base_ev_per_s"], 1),
               "fed_ev_per_s": round(stats["ev_per_s"], 1)})


def err_record(stats: dict, provenance: dict) -> dict:
    from .schema import make_record
    return make_record(
        config="accuracy-observed-err", metric="cms_observed_err",
        unit="pct", value=round(stats["observed_err_pct"], 5),
        stages={"audit_feed": {"events": float(stats["events"]),
                               "calls": float(stats["audited_keys"])}},
        provenance=provenance,
        extra={"events": stats["events"], "vocab": stats["vocab"],
               "depth": stats["depth"], "log2_width": stats["log2_width"],
               "capacity": stats["capacity"],
               "audited_keys": stats["audited_keys"],
               "observed_err_pct": round(stats["observed_err_pct"], 5),
               "max_err_pct": round(stats["max_err_pct"], 5),
               "bound_pct": round(stats["bound_pct"], 5)})


def publish(*, batch: int = 1 << 14, capacity: int = 1024,
            seconds: float = 0.5, events: int = 2_000_000,
            ledger: str | None = None) -> list[dict]:
    """Measure all three series and append the records to the ledger;
    returns the records (schema-validated by the append path)."""
    from .ledger import append_record
    from .provenance import acquire_provenance

    prov = acquire_provenance("auto")
    feed = measure_feed(batch=batch, capacity=capacity, seconds=seconds)
    err = measure_observed_err(events=events, capacity=capacity)
    records = [feed_record(feed, prov), overhead_record(feed, prov),
               err_record(err, prov)]
    for rec in records:
        append_record(rec, path=ledger)
    return records


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="accuracy-audit-plane micro-bench → perf ledger")
    ap.add_argument("--ledger", default=None,
                    help="ledger path (default: the repo ledger)")
    ap.add_argument("--batch", type=int, default=1 << 14)
    ap.add_argument("--capacity", type=int, default=1024)
    ap.add_argument("--seconds", type=float, default=0.5)
    ap.add_argument("--events", type=int, default=2_000_000,
                    help="stream length for the observed-error audit")
    args = ap.parse_args(argv)
    for rec in publish(batch=args.batch, capacity=args.capacity,
                       seconds=args.seconds, events=args.events,
                       ledger=args.ledger):
        e = rec["extra"]
        plat = rec["provenance"]["platform"]
        if rec["config"] == "accuracy-audit":
            print(f"accuracy-audit [{plat}]: {rec['value']:,.0f} ev/s with the "
                  f"shadow feed (batch {e['batch']}, capacity "
                  f"{e['capacity']}, overhead {e['audit_overhead']:.1%})")
        elif rec["config"] == "accuracy-overhead":
            print(f"accuracy-overhead [{plat}]: {rec['value']:.4f} "
                  f"({e['base_ev_per_s']:,.0f} -> {e['fed_ev_per_s']:,.0f} "
                  "ev/s)")
        else:
            print(f"accuracy-observed-err [{plat}]: {rec['value']:.5f}% observed "
                  f"vs {e['bound_pct']:.5f}% bound ({e['audited_keys']} "
                  f"key(s) audited over {e['events']:,} events)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
