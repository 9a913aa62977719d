"""Readers of the program's telemetry registry: deltas between the snapshot
taken when the window opened and the one taken when the counters closed."""

from __future__ import annotations


def total(snap: dict, name: str) -> float:
    """A metric family's value summed over its label sets."""
    return sum(v for k, v in snap.items()
               if k == name or k.startswith(name + "{"))


def _delta(run, name: str) -> float:
    return total(run.tap.snap_end, name) - total(run.tap.snap_start, name)


def rate(run, counter: str) -> float | None:
    secs = run.tap.counters_end - run.tap.window_start
    return _delta(run, counter) / secs if secs > 0 else None


def mean_ms(run, histogram: str) -> float | None:
    n = _delta(run, histogram + "_count")
    return 1e3 * _delta(run, histogram + "_sum") / n if n > 0 else None
