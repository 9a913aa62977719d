"""Readers of the reduced device trace (tracereduce.py: reduce_trace). Without a
traced run there is nothing to read and every reader returns None."""

from __future__ import annotations

import work


def _seconds_per_run(run, programs: list[str]) -> float | None:
    """Device seconds per execution of the named programs (`XLA Modules`
    names as the trace shows them, without the fingerprint)."""
    if not run.trace:
        return None
    rows = [run.trace["programs"][p] for p in programs
            if p in run.trace["programs"]]
    runs = sum(n for _s, n in rows)
    return sum(s for s, _n in rows) / runs if runs else None


def program_ms_per_run(run, programs: list[str]) -> float | None:
    s = _seconds_per_run(run, programs)
    return None if s is None else 1e3 * s


def update_roofline(run, programs: list[str]) -> float | None:
    s = _seconds_per_run(run, programs)
    if s is None:
        return None
    g = run.geometry
    w = work.update_work(run.batch_size, run.staged_lanes, g["depth"],
                         g["topk"])
    least, _binds = work.least_seconds(w, work.peaks(run.device_kind))
    return 100.0 * least / s


def idle_share(run) -> float | None:
    r = run.trace
    if not r or r["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - r["busiest_busy_s"] / r["window_s"])


def collective_ms_per_harvest(run) -> float | None:
    r = run.trace
    if not r or not r["collective_s"] or not r["harvests"]:
        return None
    return 1e3 * r["collective_s"] / r["harvests"]
