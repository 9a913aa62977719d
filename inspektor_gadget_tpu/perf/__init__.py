"""Perf-observability plane: provenance-stamped PerfRecords, append-only
ledger, and noise-aware regression comparison.

The third leg of the observability stool (PR 1 metrics, PR 2 tracing):
perf numbers become machine-written, schema-validated artifacts with
provenance the docs cannot drift from. Surfaces: `ig-tpu bench
compare|report|import` and `tools/check_perf_claims.py`.
"""

from .compare import (
    CompareResult,
    compare_ledger,
    compare_record,
    render_compare,
    render_report,
)
from .ledger import (
    DEFAULT_LEDGER,
    append_record,
    bench_json_to_record,
    import_bench_files,
    ledger_path,
    read_ledger,
)
from .provenance import build_provenance, probe_block
from .schema import SCHEMA_ID, make_record, validate_record

__all__ = [
    "CompareResult", "DEFAULT_LEDGER", "SCHEMA_ID", "append_record",
    "bench_json_to_record", "build_provenance", "compare_ledger",
    "compare_record", "import_bench_files", "ledger_path", "make_record",
    "probe_block", "read_ledger", "render_compare", "render_report",
    "validate_record",
]
