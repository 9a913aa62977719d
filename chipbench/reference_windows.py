"""The plain reference of event-time windows, and the comparison a recorder
of every event's (key, ts) holds sealed windows to.

The tap records no event's `ts`, so `correct` cannot see which window an
event belongs to (PERF.md section 7). This module is what can: given every
event's folded key and its `ts`, it assigns each event to the window
[k W, (k+1) W) of its own time with numpy alone, counts each window exactly
and compares a run's sealed windows with it, window by window, under the
configuration's envelopes:

  events       a window's count == the exact count of its events (the
               program counts each window in integers): gap 0
  late         events sealed as late, 0 where no event is delayed past
               history-lateness; sealed events + late == every event
  alignment    each window starts at a multiple of W and is W long, and
               every window the stream reaches is sealed once
  count-min    a window's top-k counts never under its exact counts
  HLL          relative error within 4 x 1.04/sqrt(2^hll-p)
  entropy      the window's histogram's entropy against the exact counts
               collapsed into 2^entropy-log2-width buckets by the harness's
               own hash (reference.py), within limits.entropy_gap_bits

It imports nothing of the program. `compare` takes the sealed windows as
objects with the fields of a decoded window (start_ts, end_ts, events,
late, hll, ent, topk_keys, topk_counts).
"""

from __future__ import annotations

import math

import numpy as np

import reference


def window_of(ts: np.ndarray, width_s: float) -> np.ndarray:
    """Each event's window index k: [k W, (k+1) W) holds its ts (ns)."""
    return (np.asarray(ts, np.uint64)
            // np.uint64(round(width_s * 1e9))).astype(np.int64)


def hll_estimate(registers: np.ndarray) -> float:
    """HyperLogLog (Flajolet et al.) of one register array, with linear
    counting below 2.5 m."""
    r = np.asarray(registers, np.float64)
    m = len(r)
    alpha = {16: 0.673, 32: 0.697, 64: 0.709}.get(m, 0.7213 / (1 + 1.079 / m))
    raw = alpha * m * m / np.sum(np.exp2(-r))
    zeros = int(np.count_nonzero(r == 0))
    if raw <= 2.5 * m and zeros:
        return m * math.log(m / zeros)
    return float(raw)


def collapsed_entropy_bits(keys: np.ndarray, counts: np.ndarray,
                           log2_width: int) -> float:
    """Entropy of exact counts seen through a histogram of 2^log2_width
    buckets: the mean over reference.SALTS bucketings of the harness's hash."""
    shift = np.uint64(64 - log2_width)
    return float(np.mean([
        reference.entropy_bits(np.bincount(
            (reference._mix(keys, salt) >> shift).astype(np.int64),
            weights=counts, minlength=1 << log2_width))
        for salt in range(1, reference.SALTS + 1)]))


def exact_windows(keys: np.ndarray, ts: np.ndarray, width_s: float
                  ) -> dict[int, tuple[np.ndarray, np.ndarray]]:
    """{k: (its distinct keys, their exact counts)} for every window the
    stream reaches."""
    keys = np.asarray(keys, np.uint32)
    k = window_of(ts, width_s)
    order = np.argsort(k, kind="stable")
    k, keys = k[order], keys[order]
    bounds = np.flatnonzero(np.diff(k)) + 1
    out = {}
    for lo, hi in zip(np.r_[0, bounds], np.r_[bounds, len(k)]):
        out[int(k[lo])] = np.unique(keys[lo:hi], return_counts=True)
    return out


def compare(windows: list, keys: np.ndarray, ts: np.ndarray, *,
            width_s: float, geometry: dict, limits: dict,
            expect_late: bool = False) -> tuple[bool, dict]:
    """Sealed `windows` against the exact windows of the recorded stream.
    With `expect_late` (events delayed past the lateness) late events are
    allowed, a window may hold fewer events than its time had (which of
    them came late is known in total only: sealed + late == all is held
    instead) and one whose events all came late may be missing. Returns
    (correct, {name: {value, limit}})."""
    rows: dict[str, tuple[float, float | None]] = {}

    def worst(name: str, value: float, limit: float | None) -> None:
        if name not in rows or value > rows[name][0]:
            rows[name] = (float(value), limit)

    width_ns = round(width_s * 1e9)
    exact = exact_windows(keys, ts, width_s)
    late = sum(int(w.late) for w in windows)
    sealed = sum(int(w.events) for w in windows)
    worst("windows_sealed", float(len(windows)), None)
    worst("late", float(late), None if expect_late else 0.0)
    worst("accounting_gap", abs(sealed + late - len(keys)), 0.0)
    by_k: dict[int, object] = {}
    misaligned = 0
    for w in windows:
        start = round(float(w.start_ts) * 1e9)
        k = start // width_ns
        misaligned += (start % width_ns != 0 or
                       round(float(w.end_ts) * 1e9) - start != width_ns or
                       k in by_k)
        by_k[k] = w
    worst("misaligned", float(misaligned), 0.0)
    # a window every event of which came late never opens
    worst("windows_missing", float(len(set(exact) - set(by_k))),
          None if expect_late else 0.0)
    # one that holds no event of its own may seal to carry late ones
    worst("windows_unknown", float(sum(
        1 for k in set(by_k) - set(exact) if by_k[k].events)), 0.0)
    d_bound = 4.0 * 1.04 / math.sqrt(1 << geometry["hll-p"])
    for k, (uniq, counts) in exact.items():
        w = by_k.get(k)
        if w is None:
            continue
        gap = int(counts.sum()) - int(w.events)
        # a late event is one its window did not hold: never one too many
        worst("events_gap", abs(gap) if not expect_late or gap < 0 else 0,
              0.0)
        if expect_late:
            continue
        hh = np.asarray(w.topk_keys, np.uint32)
        est = np.asarray(w.topk_counts, np.int64)
        pos = np.searchsorted(uniq, hh).clip(0, len(uniq) - 1)
        true = np.where(uniq[pos] == hh, counts[pos], 0)
        worst("hh_under", max(int((true - est).max(initial=0)), 0), 0.0)
        worst("distinct_err",
              abs(hll_estimate(w.hll) - len(uniq)) / len(uniq), d_bound)
        ent = np.asarray(w.ent, np.float64)
        worst("entropy_gap_bits", abs(
            reference.entropy_bits(ent) - collapsed_entropy_bits(
                uniq, counts, geometry["entropy-log2-width"])),
            limits["entropy_gap_bits"])
    ok = bool(windows) and all(
        limit is None or value <= limit for value, limit in rows.values())
    return ok, {k: {"value": v, "limit": lim} for k, (v, lim) in rows.items()}
