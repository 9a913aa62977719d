"""DDSketch quantile-plane micro-bench → schema-valid PerfRecords.

ISSUE 16 satellite: the quantile plane's cost model is two claims —
(1) the standalone DDSketch batch fold absorbs values at device speed
(on the hot path the fused kernel carries the plane as one extra grid
plane, so this is the upper bound on what the plane adds), and (2) the
bucket-wise merge is cheap enough that cluster folds (psum harvest,
sealed-window pushdown) are free relative to ingest. This bench measures
both and publishes one record per series (`quantile-update` /
`qt_update` in events/sec, `quantile-merge` / `qt_merge` in merges/sec)
to the perf ledger, so a plane regression gates exactly like a speed
regression via `bench compare`.

Run standalone (`python -m inspektor_gadget_tpu.perf.quantile_bench
[--ledger PATH] [--batch N] [--buckets N]`) or from tests with tiny
shapes.
"""

from __future__ import annotations

import argparse
import time

import numpy as np


def _latencies(batch: int, seed: int = 42) -> np.ndarray:
    """Synthetic ns-domain latencies: lognormal body (~50µs median) with
    a heavy tail — the shape a syscall-latency lane actually carries."""
    rng = np.random.default_rng(seed)
    v = rng.lognormal(mean=np.log(50_000.0), sigma=1.2, size=batch)
    return v.astype(np.float32)


def measure_update(*, batch: int = 1 << 15, n_buckets: int = 2048,
                   alpha: float = 0.01, seconds: float = 1.0) -> dict:
    """Events/sec through the jitted standalone dd_update at one batch
    shape (donating steps, periodic sync)."""
    import jax
    import jax.numpy as jnp

    from ..ops.quantiles import dd_init, dd_update

    step = jax.jit(dd_update, donate_argnums=0)
    s = dd_init(alpha, n_buckets, min_value=1.0)
    values = jnp.asarray(_latencies(batch))
    s = step(s, values)
    jax.block_until_ready(s.counts)  # compile outside the window
    steps = 0
    t0 = time.perf_counter()
    while True:
        s = step(s, values)
        steps += 1
        if steps % 8 == 0:
            jax.block_until_ready(s.counts)
            if time.perf_counter() - t0 >= seconds:
                break
    jax.block_until_ready(s.counts)
    elapsed = max(time.perf_counter() - t0, 1e-9)
    return {
        "batch": batch, "n_buckets": n_buckets, "alpha": alpha,
        "steps": steps, "events": steps * batch, "seconds": elapsed,
        "ev_per_s": steps * batch / elapsed,
    }


def measure_merge(*, n_buckets: int = 2048, alpha: float = 0.01,
                  seconds: float = 0.5) -> dict:
    """Merges/sec of the jitted bucket-wise dd_merge — the per-pair cost
    a client-side fold of N nodes' sealed windows pays N-1 times."""
    import jax
    import jax.numpy as jnp

    from ..ops.quantiles import dd_init, dd_merge, dd_update

    merge = jax.jit(dd_merge)
    a = dd_init(alpha, n_buckets, min_value=1.0)
    a = dd_update(a, jnp.asarray(_latencies(4096, seed=7)))
    b = dd_update(dd_init(alpha, n_buckets, min_value=1.0),
                  jnp.asarray(_latencies(4096, seed=8)))
    jax.block_until_ready(merge(a, b).counts)  # compile
    steps = 0
    t0 = time.perf_counter()
    while True:
        a = merge(a, b)
        steps += 1
        if steps % 16 == 0:
            jax.block_until_ready(a.counts)
            if time.perf_counter() - t0 >= seconds:
                break
    jax.block_until_ready(a.counts)
    elapsed = max(time.perf_counter() - t0, 1e-9)
    return {
        "n_buckets": n_buckets, "alpha": alpha, "steps": steps,
        "seconds": elapsed, "merges_per_s": steps / elapsed,
    }


def update_record(stats: dict, provenance: dict) -> dict:
    from .schema import make_record
    return make_record(
        config="quantile-update", metric="qt_update", unit="events/sec",
        value=stats["ev_per_s"],
        stages={"qt_update": {"seconds": stats["seconds"],
                              "events": float(stats["events"]),
                              "ev_per_s": stats["ev_per_s"],
                              "calls": float(stats["steps"])}},
        provenance=provenance,
        extra={"batch": stats["batch"], "n_buckets": stats["n_buckets"],
               "alpha": stats["alpha"]})


def merge_record(stats: dict, provenance: dict) -> dict:
    from .schema import make_record
    return make_record(
        config="quantile-merge", metric="qt_merge", unit="merges/sec",
        value=stats["merges_per_s"],
        stages={"qt_merge": {"seconds": stats["seconds"],
                             "calls": float(stats["steps"])}},
        provenance=provenance,
        extra={"n_buckets": stats["n_buckets"], "alpha": stats["alpha"]})


def publish(*, batch: int = 1 << 15, n_buckets: int = 2048,
            alpha: float = 0.01, seconds: float = 1.0,
            ledger: str | None = None) -> list[dict]:
    """Measure both series and append the records to the ledger;
    returns the records (schema-validated by the append path)."""
    from .ledger import append_record
    from .provenance import acquire_provenance

    prov = acquire_provenance("auto")
    records = [
        update_record(measure_update(batch=batch, n_buckets=n_buckets,
                                     alpha=alpha, seconds=seconds), prov),
        merge_record(measure_merge(n_buckets=n_buckets, alpha=alpha,
                                   seconds=min(seconds, 0.5)), prov),
    ]
    for rec in records:
        append_record(rec, path=ledger)
    return records


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="DDSketch quantile-plane micro-bench → perf ledger")
    ap.add_argument("--ledger", default=None,
                    help="ledger path (default: the repo ledger)")
    ap.add_argument("--batch", type=int, default=1 << 15)
    ap.add_argument("--buckets", type=int, default=2048)
    ap.add_argument("--alpha", type=float, default=0.01)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    for rec in publish(batch=args.batch, n_buckets=args.buckets,
                       alpha=args.alpha, seconds=args.seconds,
                       ledger=args.ledger):
        e = rec["extra"]
        plat = rec["provenance"]["platform"]
        if rec["config"] == "quantile-update":
            print(f"quantile-update [{plat}]: {rec['value']:,.0f} ev/s "
                  f"(batch {e['batch']}, {e['n_buckets']} buckets, "
                  f"alpha {e['alpha']:g})")
        else:
            print(f"quantile-merge [{plat}]: {rec['value']:,.0f} merges/s "
                  f"({e['n_buckets']} buckets)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
