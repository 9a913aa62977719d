"""Invertible-sketch micro-bench → schema-valid PerfRecords.

ISSUE 15 satellite: the invertible plane's cost model is two claims —
(1) the standalone update absorbs batches at device speed (on the hot
path the fused kernel carries it as extra grid planes, so this is the
upper bound on what the plane adds), and (2) decode of merged state
recovers keys at a rate that makes per-harvest decoding viable. This
bench measures both and publishes one record per series (`inv-update` /
`inv_update` in events/sec, `inv-decode` / `inv_decode` in keys/sec) to
the perf ledger, so a plane regression gates exactly like a speed
regression via `bench compare`.

Run standalone (`python -m inspektor_gadget_tpu.perf.invertible_bench
[--ledger PATH] [--batch N] [--keys N]`) or from tests with tiny shapes.
"""

from __future__ import annotations

import argparse
import time

import numpy as np


def measure_update(*, batch: int = 1 << 15, rows: int = 3,
                   log2_buckets: int = 12, seconds: float = 1.0) -> dict:
    """Events/sec through the jitted standalone inv_update at one batch
    shape (donating steps, periodic sync)."""
    import jax
    import jax.numpy as jnp

    from ..ops.invertible import inv_init, inv_update

    step = jax.jit(inv_update, donate_argnums=0)
    s = inv_init(rows, log2_buckets)
    rng = np.random.default_rng(42)
    keys = jnp.asarray(rng.integers(1, 1 << 32, batch).astype(np.uint32))
    w = jnp.ones(batch, jnp.int32)
    s = step(s, keys, w)
    jax.block_until_ready(s.count)  # compile outside the window
    steps = 0
    t0 = time.perf_counter()
    while True:
        s = step(s, keys, w)
        steps += 1
        if steps % 8 == 0:
            jax.block_until_ready(s.count)
            if time.perf_counter() - t0 >= seconds:
                break
    jax.block_until_ready(s.count)
    elapsed = max(time.perf_counter() - t0, 1e-9)
    return {
        "batch": batch, "rows": rows, "log2_buckets": log2_buckets,
        "steps": steps, "events": steps * batch, "seconds": elapsed,
        "ev_per_s": steps * batch / elapsed,
    }


def measure_decode(*, n_keys: int = 2048, rows: int = 3,
                   log2_buckets: int = 12, reps: int = 3) -> dict:
    """Keys/sec recovered by a full decode (device peel + host finisher)
    of a sketch loaded to `n_keys` distinct keys — kept under the
    documented capacity so the measured decode is COMPLETE (asserted;
    a partial decode would publish a meaningless rate)."""
    import jax
    import jax.numpy as jnp

    from ..ops.invertible import (inv_capacity, inv_decode, inv_init,
                                  inv_update)

    cap = inv_capacity(rows, log2_buckets)
    if n_keys > cap:
        raise ValueError(f"n_keys {n_keys} exceeds decode capacity {cap} "
                         f"for rows={rows} log2_buckets={log2_buckets}")
    rng = np.random.default_rng(7)
    keys = rng.choice(
        np.arange(1, 1 << 22, dtype=np.uint32), size=n_keys,
        replace=False)
    # cap at a value with few trailing zero bits: counts divisible by
    # 2^17+ are the documented decode blind spot and a power-of-two clip
    # would manufacture exactly that pathology
    counts = rng.zipf(1.4, size=n_keys).clip(1, 999_999).astype(np.int64)
    step = jax.jit(inv_update, donate_argnums=0)
    s = inv_init(rows, log2_buckets)
    s = step(s, jnp.asarray(keys), jnp.asarray(counts.astype(np.int32)))
    jax.block_until_ready(s.count)
    best = None
    for _ in range(reps):
        t0 = time.perf_counter()
        dec = inv_decode(s)
        dt = max(time.perf_counter() - t0, 1e-9)
        if not dec.complete or dec.recovered != n_keys:
            raise AssertionError(
                f"decode under capacity must be complete: recovered "
                f"{dec.recovered}/{n_keys}, complete={dec.complete}")
        best = dt if best is None else min(best, dt)
    return {
        "keys": n_keys, "rows": rows, "log2_buckets": log2_buckets,
        "capacity": cap, "seconds": best,
        "keys_per_s": n_keys / best, "complete": True,
    }


def update_record(stats: dict, provenance: dict) -> dict:
    from .schema import make_record
    return make_record(
        config="inv-update", metric="inv_update", unit="events/sec",
        value=stats["ev_per_s"],
        stages={"inv_update": {"seconds": stats["seconds"],
                               "events": float(stats["events"]),
                               "ev_per_s": stats["ev_per_s"],
                               "calls": float(stats["steps"])}},
        provenance=provenance,
        extra={"batch": stats["batch"], "rows": stats["rows"],
               "log2_buckets": stats["log2_buckets"]})


def decode_record(stats: dict, provenance: dict) -> dict:
    from .schema import make_record
    return make_record(
        config="inv-decode", metric="inv_decode", unit="keys/sec",
        value=stats["keys_per_s"],
        stages={"inv_decode": {"seconds": stats["seconds"],
                               "events": float(stats["keys"])}},
        provenance=provenance,
        extra={"keys": stats["keys"], "rows": stats["rows"],
               "log2_buckets": stats["log2_buckets"],
               "capacity": stats["capacity"],
               "complete": 1.0})


def publish(*, batch: int = 1 << 15, n_keys: int = 2048,
            rows: int = 3, log2_buckets: int = 12,
            seconds: float = 1.0, ledger: str | None = None) -> list[dict]:
    """Measure both series and append the records to the ledger;
    returns the records (schema-validated by the append path)."""
    from .ledger import append_record
    from .provenance import acquire_provenance

    prov = acquire_provenance("auto")
    records = [
        update_record(measure_update(batch=batch, rows=rows,
                                     log2_buckets=log2_buckets,
                                     seconds=seconds), prov),
        decode_record(measure_decode(n_keys=n_keys, rows=rows,
                                     log2_buckets=log2_buckets), prov),
    ]
    for rec in records:
        append_record(rec, path=ledger)
    return records


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="invertible-sketch micro-bench → perf ledger")
    ap.add_argument("--ledger", default=None,
                    help="ledger path (default: the repo ledger)")
    ap.add_argument("--batch", type=int, default=1 << 15)
    ap.add_argument("--keys", type=int, default=2048)
    ap.add_argument("--rows", type=int, default=3)
    ap.add_argument("--log2-buckets", type=int, default=12)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    for rec in publish(batch=args.batch, n_keys=args.keys, rows=args.rows,
                       log2_buckets=args.log2_buckets,
                       seconds=args.seconds, ledger=args.ledger):
        e = rec["extra"]
        plat = rec["provenance"]["platform"]
        if rec["config"] == "inv-update":
            print(f"inv-update [{plat}]: {rec['value']:,.0f} ev/s "
                  f"(batch {e['batch']}, {e['rows']}x2^{e['log2_buckets']})")
        else:
            print(f"inv-decode [{plat}]: {rec['value']:,.0f} keys/s "
                  f"({e['keys']} keys, capacity {e['capacity']}, "
                  "complete)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
