"""Append-only perf ledger: benchmarks/ledger/PERF.jsonl.

The machine-written perf history the docs cannot drift from (the role of
inspektor-gadget's CI benchmark dashboard, kept in-tree): one JSON line
per PerfRecord, appended atomically, never rewritten. `ig-tpu bench
compare` baselines against it; `tools/check_perf_claims.py` checks doc
numbers against it.

Append discipline: the record is validated first (a ledger line that
fails the schema is worse than no line), then written through the shared
utils/journal.py atomic-append + torn-tail-tolerant-read discipline (one
O_APPEND write per line; reads skip-and-report unusable lines) — the
same recovery logic the alert webhook sink and the capture plane use,
kept in exactly one place.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Iterable

from ..utils.journal import append_line, read_jsonl
from .schema import SCHEMA_ID, make_record, validate_record

DEFAULT_LEDGER = os.path.join("benchmarks", "ledger", "PERF.jsonl")


def ledger_path(path: str | None = None) -> str:
    return path or os.environ.get("IG_PERF_LEDGER", DEFAULT_LEDGER)


@dataclasses.dataclass
class LedgerRead:
    records: list[dict]
    skipped: list[str]          # 'line N: why' for unusable lines


def append_record(rec: dict, path: str | None = None) -> str:
    """Validate + atomically append one record; returns the path used."""
    errors = validate_record(rec)
    if errors:
        raise ValueError("refusing to append invalid PerfRecord: "
                         + "; ".join(errors))
    p = ledger_path(path)
    append_line(p, rec)
    return p


def read_ledger(path: str | None = None) -> LedgerRead:
    """All parseable, schema-valid records in append order. Unusable
    lines are reported, not fatal: a crash mid-append must not take the
    whole history down with it."""
    def _validate(rec: dict) -> str | None:
        errors = validate_record(rec)
        if not errors:
            return None
        return errors[0] + (f" +{len(errors) - 1} more" if len(errors) > 1
                            else "")

    jr = read_jsonl(ledger_path(path), on_bad="skip", validate=_validate)
    return LedgerRead(jr.records, jr.skipped)


# ---------------------------------------------------------------------------
# Import of driver-written BENCH_r*.json artifacts (pre-ledger history)
# ---------------------------------------------------------------------------

def bench_json_to_record(doc: dict, source: str = "") -> dict:
    """Convert one driver BENCH_r*.json document (or its bare result
    line) into a PerfRecord. Provenance that the old artifact never
    carried is recorded as unknown — imported history is explicitly
    second-class, never dressed up as stamped at the source."""
    parsed = doc.get("parsed") if "parsed" in doc else doc
    if not isinstance(parsed, dict) or "value" not in parsed:
        raise ValueError(f"{source or 'document'}: no parsed benchmark "
                         "result to import")
    extra = dict(parsed.get("extra") or {})
    platform = str(extra.get("platform", "unknown") or "unknown")
    if platform not in ("tpu", "cpu", "gpu", "none"):
        platform = "unknown"
    degraded = bool(extra.get("degraded", False))
    stages: dict[str, dict[str, float]] = {}
    if isinstance(extra.get("host_plane_ev_per_s"), (int, float)):
        stages["pop"] = {"ev_per_s": float(extra["host_plane_ev_per_s"])}
    if isinstance(extra.get("device_plane_ev_per_s"), (int, float)):
        stages["bundle_update"] = {
            "ev_per_s": float(extra["device_plane_ev_per_s"])}
    if isinstance(extra.get("merge_ms_p50"), (int, float)):
        stages["merge"] = {"ms_p50": float(extra["merge_ms_p50"])}
    probe = {"outcome": "imported", "attempts": []}
    err = extra.get("error")
    if isinstance(err, dict) and err:
        probe["detail"] = "; ".join(f"{k}: {v}" for k, v in err.items())
    prov = {
        "git_sha": "unknown",
        "git_dirty": False,
        "host": {"hostname": "unknown", "machine": "unknown",
                 "python": "unknown"},
        "platform": platform,
        "degraded": degraded,
        "probe": probe,
    }
    imported_extra = {"imported_from": source or "bench-json",
                      **{k: v for k, v in extra.items()
                         if isinstance(v, (int, float, str, bool))}}
    if "n" in doc:
        imported_extra["round"] = doc["n"]
    return make_record(
        config="bench.e2e",
        metric=str(parsed.get("metric", "sketch_ingest_throughput_e2e")),
        unit=str(parsed.get("unit", "events/sec/chip")),
        value=float(parsed["value"]),
        stages=stages,
        provenance=prov,
        extra=imported_extra,
    )


def import_bench_files(paths: Iterable[str],
                       ledger: str | None = None) -> tuple[int, list[str]]:
    """Append a record per importable BENCH file; returns (imported,
    ['path: why skipped']). Already-imported files (same imported_from)
    are skipped so re-running is idempotent."""
    existing = {r.get("extra", {}).get("imported_from")
                for r in read_ledger(ledger).records}
    n = 0
    skipped: list[str] = []
    for path in paths:
        name = os.path.basename(path)
        if name in existing:
            skipped.append(f"{path}: already imported")
            continue
        try:
            with open(path, encoding="utf-8") as f:
                doc = json.load(f)
            rec = bench_json_to_record(doc, source=name)
        except (OSError, ValueError, json.JSONDecodeError) as e:
            skipped.append(f"{path}: {e}")
            continue
        append_record(rec, ledger)
        n += 1
    return n, skipped


__all__ = ["DEFAULT_LEDGER", "LedgerRead", "SCHEMA_ID", "append_record",
           "bench_json_to_record", "import_bench_files", "ledger_path",
           "read_ledger"]
