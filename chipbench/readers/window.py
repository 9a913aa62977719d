"""Readers of the end-to-end metrics: the tap's integers and wall times over
the whole window (all the work over all the time; every summary's lag)."""

from __future__ import annotations

import statistics

MIN_LAGS = 20      # fewer summaries than this make no percentile


def events_per_s(run) -> float:
    t = run.tap
    return t.absorbed(t.first_batch, t.last_batch) / (
        t.window_end - t.window_start)


def lag_ms(run, quantile: float) -> float | None:
    lags = run.tap.lags_ms()
    if len(lags) < MIN_LAGS:
        return None
    if quantile == 0.5:
        return float(statistics.median(lags))
    return float(statistics.quantiles(lags, n=20)[round(quantile * 20) - 1])


def setup_s(run) -> float:
    return run.setup_s


def summary_gap_max_ms(run) -> float | None:
    """Widest gap between two summaries (per-layer: the stall a harvest or a
    seal puts between them), over the part of the window the counters cover."""
    t = run.tap
    times = [t.window_start] + [at for at, _b, _s in t.window_summaries()
                                if at <= t.counters_end]
    if len(times) < 2:
        return None
    return 1e3 * max(b - a for a, b in zip(times, times[1:]))
