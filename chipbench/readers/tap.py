"""Readers of the host tap: integer counts of what the source offered and
the ring shed, over the part of the window the counters cover."""

from __future__ import annotations


def _offered(run) -> tuple[int, int, float]:
    t = run.tap
    first, last = t.first_batch, t.counters_batch
    if last <= first:
        return 0, 0, 0.0
    return (t.absorbed(first, last), t.shed(first, last),
            t.counters_end - t.window_start)


def offered_rate(run) -> float | None:
    absorbed, shed, secs = _offered(run)
    return (absorbed + shed) / secs if secs > 0 else None


def drop_share(run) -> float | None:
    absorbed, shed, _secs = _offered(run)
    return 100.0 * shed / (absorbed + shed) if absorbed + shed else None
