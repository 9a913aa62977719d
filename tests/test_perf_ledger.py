"""Perf-observability plane: PerfRecord schema, append-only ledger and
noise-aware regression comparison (the acceptance gates of the perf plane).

Key provenance contracts pinned here:

- a record without provenance (or with a non-bool degraded flag) cannot
  enter the ledger;
- `degraded: true` records are never baseline material, and a TPU
  candidate whose only history is degraded/CPU records is REFUSED
  (exit 3), not silently compared;
- a synthetic 20% throughput regression exits nonzero; an in-band run
  exits zero;
- concurrent appends never interleave bytes (one os.write on O_APPEND).
"""

from __future__ import annotations

import json
import threading

import pytest

from inspektor_gadget_tpu.perf import (
    append_record,
    bench_json_to_record,
    compare_record,
    make_record,
    read_ledger,
    validate_record,
)
from inspektor_gadget_tpu.perf.compare import (
    RC_REGRESSION,
    RC_REFUSED,
    compare_ledger,
    render_compare,
    render_report,
)
from inspektor_gadget_tpu.perf.schema import SCHEMA_ID


def prov(platform="tpu", degraded=False, sha="deadbeef"):
    return {
        "git_sha": sha, "git_dirty": False,
        "host": {"hostname": "h", "machine": "x86_64", "python": "3.12"},
        "platform": platform, "degraded": degraded,
        "probe": {"outcome": "ok", "attempts": []},
    }


def rec(value, platform="tpu", degraded=False, config="bench.e2e", ts=None):
    return make_record(
        config=config, metric="sketch_ingest_throughput_e2e",
        unit="events/sec/chip", value=value,
        stages={"pop": {"ev_per_s": value * 1.5, "seconds": 1.0}},
        provenance=prov(platform, degraded),
        ts=ts or f"2026-08-0{1 + (int(value) % 8)}T00:00:00+00:00",
    )


# ---------------------------------------------------------------------------
# schema validation
# ---------------------------------------------------------------------------

def test_valid_record_passes():
    r = rec(100e6)
    assert r["schema"] == SCHEMA_ID
    assert r["direction"] == "higher_better"  # derived from the /s unit
    assert validate_record(r) == []


def test_missing_provenance_rejected():
    r = rec(100e6)
    del r["provenance"]
    errs = validate_record(r)
    assert any("provenance" in e for e in errs)


def test_bad_fields_rejected():
    r = rec(100e6)
    r["provenance"]["degraded"] = "no"       # not a bool
    r["provenance"]["platform"] = "quantum"  # not a platform
    r["stages"]["pop"]["ev_per_s"] = "fast"  # not a number
    r["value"] = "big"
    errs = "\n".join(validate_record(r))
    for frag in ("degraded", "platform", "ev_per_s", "$.value"):
        assert frag in errs, errs


def test_make_record_refuses_invalid():
    with pytest.raises(ValueError, match="provenance"):
        make_record(config="c", metric="m", unit="ev/s", value=1.0,
                    stages={}, provenance={"git_sha": "x"})


def test_latency_unit_defaults_lower_better():
    r = make_record(config="c", metric="merge_latency", unit="ms",
                    value=1.0, stages={}, provenance=prov())
    assert r["direction"] == "lower_better"


# ---------------------------------------------------------------------------
# ledger append/read
# ---------------------------------------------------------------------------

def test_append_and_read_roundtrip(tmp_path):
    p = str(tmp_path / "PERF.jsonl")
    append_record(rec(1e6), p)
    append_record(rec(2e6), p)
    lr = read_ledger(p)
    assert [r["value"] for r in lr.records] == [1e6, 2e6]
    assert lr.skipped == []


def test_append_refuses_invalid(tmp_path):
    p = str(tmp_path / "PERF.jsonl")
    bad = rec(1e6)
    bad["provenance"]["degraded"] = "maybe"
    with pytest.raises(ValueError, match="refusing to append"):
        append_record(bad, p)
    assert read_ledger(p).records == []


def test_read_tolerates_corrupt_and_truncated_lines(tmp_path):
    p = tmp_path / "PERF.jsonl"
    append_record(rec(1e6), str(p))
    with open(p, "a") as f:
        f.write('{"not": "a record"}\n')
        f.write('{"schema": "ig-tpu/perf-record/v1", "trunc')  # crash tail
    lr = read_ledger(str(p))
    assert len(lr.records) == 1
    assert len(lr.skipped) == 2


def test_append_atomicity_under_concurrency(tmp_path):
    p = str(tmp_path / "PERF.jsonl")
    n_threads, per_thread = 8, 25

    def writer(i):
        for j in range(per_thread):
            append_record(rec(1e6 + i * 1000 + j), p)

    threads = [threading.Thread(target=writer, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    lr = read_ledger(p)
    assert lr.skipped == []                      # no interleaved bytes
    assert len(lr.records) == n_threads * per_thread


# ---------------------------------------------------------------------------
# comparator: regression gates + provenance rules
# ---------------------------------------------------------------------------

def _history(values, **kw):
    return [rec(v, ts=f"2026-07-{i + 1:02d}T00:00:00+00:00", **kw)
            for i, v in enumerate(values)]


def test_comparator_flags_20pct_regression():
    hist = _history([100e6, 101e6, 99e6, 100.5e6, 99.5e6])
    res = compare_record(rec(80e6), hist)
    assert res.status == "regression"
    assert res.rc == RC_REGRESSION != 0
    assert res.pool_n == 5


def test_comparator_passes_in_band_run():
    hist = _history([100e6, 101e6, 99e6, 100.5e6, 99.5e6])
    res = compare_record(rec(97e6), hist)   # within the 15% floor band
    assert res.status == "ok"
    assert res.rc == 0


def test_degraded_records_never_baseline():
    # 5 honest TPU records at ~100M plus degraded junk at 50M: the
    # baseline must come from the honest pool only
    hist = (_history([100e6, 101e6, 99e6, 100.5e6, 99.5e6])
            + _history([50e6, 51e6], platform="cpu", degraded=True))
    res = compare_record(rec(80e6), hist)
    assert res.status == "regression"       # 80M vs 100M, not vs 50M
    assert res.pool_n == 5


def test_tpu_claim_refused_on_degraded_only_history():
    hist = _history([50e6, 51e6, 52e6], platform="cpu", degraded=True)
    res = compare_record(rec(77.9e6, platform="tpu"), hist)
    assert res.status == "refused"
    assert res.rc == RC_REFUSED != 0
    assert "refusing to baseline" in res.detail


def test_cpu_candidate_baselines_against_cpu_records():
    hist = _history([2.0e6, 2.1e6, 1.9e6], platform="cpu", degraded=False)
    res = compare_record(rec(2.05e6, platform="cpu"), hist)
    assert res.status == "ok"


def test_lower_better_direction_flips_the_gate():
    base = dict(config="m", metric="merge", unit="ms", stages={})
    hist = [make_record(value=v, provenance=prov(),
                        ts=f"2026-07-{i + 1:02d}T00:00:00+00:00", **base)
            for i, v in enumerate([1.0, 1.05, 0.95])]
    cand = make_record(value=2.0, provenance=prov(), **base)
    assert compare_record(cand, hist).status == "regression"
    cand = make_record(value=0.5, provenance=prov(), **base)
    assert compare_record(cand, hist).status == "improved"


def test_compare_ledger_end_to_end(tmp_path):
    p = str(tmp_path / "PERF.jsonl")
    for r in _history([100e6, 101e6, 99e6, 100.5e6]):
        append_record(r, p)
    append_record(rec(79e6, ts="2026-08-01T00:00:00+00:00"), p)
    results = compare_ledger(read_ledger(p).records)
    assert len(results) == 1
    assert results[0].status == "regression"
    assert "REGR" in render_compare(results)


def test_bench_cli_compare_exit_codes(tmp_path):
    from inspektor_gadget_tpu.cli.bench import main as bench_main
    p = str(tmp_path / "PERF.jsonl")
    for r in _history([100e6, 101e6, 99e6, 100.5e6]):
        append_record(r, p)
    append_record(rec(99.5e6, ts="2026-08-01T00:00:00+00:00"), p)
    assert bench_main(["compare", "--ledger", p]) == 0
    append_record(rec(75e6, ts="2026-08-02T00:00:00+00:00"), p)
    assert bench_main(["compare", "--ledger", p]) == RC_REGRESSION


def test_bench_import_and_report(tmp_path, capsys):
    from inspektor_gadget_tpu.cli.bench import main as bench_main
    bench_doc = {
        "n": 4,
        "parsed": {"metric": "sketch_ingest_throughput_e2e",
                   "value": 76359636.5, "unit": "events/sec/chip",
                   "extra": {"platform": "tpu", "degraded": False,
                             "host_plane_ev_per_s": 130455732.5,
                             "device_plane_ev_per_s": 2646607627.7,
                             "merge_ms_p50": 0.08}},
    }
    src = tmp_path / "BENCH_r04.json"
    src.write_text(json.dumps(bench_doc))
    p = str(tmp_path / "PERF.jsonl")
    assert bench_main(["import", str(src), "--ledger", p]) == 0
    # idempotent: the same artifact is not imported twice
    assert bench_main(["import", str(src), "--ledger", p]) == 0
    records = read_ledger(p).records
    assert len(records) == 1
    r = records[0]
    assert r["provenance"]["platform"] == "tpu"
    assert r["stages"]["merge"]["ms_p50"] == 0.08
    assert bench_main(["report", "--ledger", p]) == 0
    out = capsys.readouterr().out
    assert "bench.e2e" in out and "tpu" in out


def test_bench_json_to_record_marks_degraded():
    doc = {"parsed": {"metric": "m", "value": 2062450.8,
                      "unit": "events/sec/chip",
                      "extra": {"platform": "cpu", "degraded": True,
                                "error": {"tpu_probe": "timeout"}}}}
    r = bench_json_to_record(doc, "BENCH_r05.json")
    assert r["provenance"]["degraded"] is True
    assert r["provenance"]["platform"] == "cpu"
    assert "timeout" in r["provenance"]["probe"]["detail"]


def test_render_report_empty_ledger():
    assert "empty" in render_report([])


def test_same_second_records_still_baseline(tmp_path):
    """Two runs appended within the same UTC second (identical ts) are
    distinct records; the earlier one must stay baseline-eligible for
    the later one."""
    ts = "2026-08-03T00:00:00+00:00"
    older = rec(100e6, ts=ts)
    cand = rec(78e6, ts=ts)  # 22% down, same second
    res = compare_record(cand, [older, cand])
    assert res.pool_n == 1
    assert res.status == "regression"
