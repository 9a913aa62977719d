"""The plain reference and the comparison that decides `correct`.

The reference is exact: the multiset of folded keys the host handed to the
operator chain, counted with numpy. It imports nothing of the program and
takes nothing the program made except the answers it is judging. The
envelopes are the ones the configuration states (its `guarantees`), written
out here from the geometry:

  count-min   never under; over by at most N*e/width for at least
              1 - exp(-depth) of the reported keys; every key whose exact
              count exceeds the k-th largest by that bound is reported
  HLL         relative error within 4 x 1.04/sqrt(2^p)
  entropy     the program states (distinct - 1) / (2 * 2^log2w * ln 2) bits,
              which is 20 and 127 bits at the cells' sizes and can never
              fail: it is shown, not judged. Judged is the gap to what a
              histogram of that width can know: the entropy of the exact
              counts collapsed into 2^log2w buckets by a hash of the
              reference's own, mean over SALTS salts; limit from readings
  accounting  the state's events == what the host handed over, its drops ==
              the source's ring drops, sealed windows add up to the events
              (the program keeps these three in float32, exact to 2^24 and
              rounded once a step past it, so each is a relative gap with a
              limit set from readings: PERF.md section 2)

Every number compared is returned beside its limit; `correct` is all of
them holding.
"""

from __future__ import annotations

import math

import numpy as np


SALTS = 8            # independent bucketings the collapsed entropy is a mean of
ANSWERS = 12         # summaries drawn from the seed for the answers


def _mix(keys: np.ndarray, salt: int) -> np.ndarray:
    """splitmix64's finaliser over salted keys (uint64 arithmetic wraps)."""
    z = keys.astype(np.uint64) + np.uint64(
        (salt * 0x9E3779B97F4A7C15) & 0xFFFFFFFFFFFFFFFF)
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


class Exact:
    """Exact per-key counts of growing prefixes of the tapped stream."""

    def __init__(self, keys: np.ndarray, entropy_log2_width: int = 12):
        self.uniq, self.inv = np.unique(keys, return_inverse=True)
        self._counts = np.zeros(len(self.uniq), np.int64)
        self._upto = 0
        self.width = 1 << entropy_log2_width
        shift = np.uint64(64 - entropy_log2_width)
        self._buckets = [(_mix(self.uniq, salt) >> shift).astype(np.int64)
                         for salt in range(1, SALTS + 1)]

    def counts(self, prefix: int) -> np.ndarray:
        """Count of each key of `uniq` among the first `prefix` events.
        One pass over the stream in all when asked in rising order."""
        if prefix < self._upto:
            self._counts[:], self._upto = 0, 0
        self._counts += np.bincount(self.inv[self._upto:prefix],
                                    minlength=len(self.uniq))
        self._upto = prefix
        return self._counts

    def collapsed_entropy_bits(self, counts: np.ndarray) -> float:
        """Entropy of `counts` seen through a histogram of `width` buckets."""
        return float(np.mean([
            entropy_bits(np.bincount(b, weights=counts, minlength=self.width))
            for b in self._buckets]))


def entropy_bits(counts: np.ndarray) -> float:
    c = counts[counts > 0].astype(np.float64)
    p = c / c.sum()
    return float(-(p * np.log2(p)).sum())


def check_answers(s, exact: Exact, prefix: int, geometry: dict,
                  limits: dict) -> dict:
    """One summary against the exact counts of the `prefix` events it
    covers. Returns {name: (value, limit)}; a value above its limit fails."""
    counts = exact.counts(prefix)
    depth, width = geometry["depth"], 1 << geometry["log2-width"]
    bound_abs = prefix * math.e / width
    hh = [(int(k), int(c)) for k, c in s.heavy_hitters if c > 0]
    keys = np.array([k for k, _ in hh], np.uint32)
    est = np.array([c for _, c in hh], np.int64)
    pos = np.searchsorted(exact.uniq, keys)
    pos[pos >= len(exact.uniq)] = 0
    true = np.where(exact.uniq[pos] == keys, counts[pos], 0)
    # an empty report has nothing to be over or under; it fails as missing
    over = est - true if hh else np.zeros(1, np.int64)
    allowed = math.ceil(math.exp(-depth) * len(hh))
    k = len(s.heavy_hitters)
    kth = np.partition(counts, -(k + 1))[-(k + 1)] if len(counts) > k else 0
    floor = kth + bound_abs
    must = exact.uniq[counts > floor]
    missing = int(np.setdiff1d(must, keys).size)
    n_distinct = int((counts > 0).sum())
    d_err = abs(float(s.distinct) - n_distinct) / n_distinct
    d_bound = 4.0 * 1.04 / math.sqrt(1 << geometry["hll-p"])
    e_err = abs(float(s.entropy_bits) - entropy_bits(counts))
    e_stated = (n_distinct - 1) / (2.0 * exact.width * math.log(2.0))
    e_gap = abs(float(s.entropy_bits) - exact.collapsed_entropy_bits(counts))
    return {
        "hh_under": (float(max(-int(over.min()), 0)), 0.0),
        "hh_beyond_bound": (float((over > bound_abs).sum()), float(allowed)),
        "hh_missing": (float(missing), 0.0),
        "hh_worst_over": (float(over.max()), None),       # shown, not judged
        "hh_bound_abs": (bound_abs, None),
        "distinct_err": (d_err, d_bound),
        "entropy_gap_bits": (e_gap, limits["entropy_gap_bits"]),
        "entropy_err_bits": (e_err, None),                # against the exact
        "entropy_stated_bound_bits": (e_stated, None),
    }


def compare(tap, *, seed: int, geometry: dict, limits: dict,
            seal_failures: float) -> tuple[bool, dict]:
    """Judge the measured run: the accounting of EVERY summary the window
    emitted and of the sealed windows, and the answers of a sample of
    summaries drawn from the seed (ANSWERS of them) with the window's last and
    the teardown harvest always in it. Returns (correct, {name: {value, limit}})."""
    rows: dict[str, tuple[float, float | None]] = {}

    def worst(name: str, value: float, limit: float | None) -> None:
        if name not in rows or value > rows[name][0]:
            rows[name] = (value, limit)

    inside = tap.window_summaries()
    teardown = [(t, b, s) for t, b, s in tap.summaries
                if b >= tap.last_batch][-1:]
    worst("summaries_in_window", float(len(inside)), None)
    for _t, b, s in inside + teardown:
        # a summary closes batch b when the tap for b has not fired yet;
        # the teardown harvest comes after the last tap
        last = min(b, tap.batches - 1)
        n = int(tap.end[last])
        shed = int(tap.drops[last])
        worst("events_gap", abs(s.events - n) / n, limits["events_gap"])
        worst("drops_gap", abs(s.drops - shed) / (n + shed),
              limits["drops_gap"])
    sealed = sum(h["events"] for _t, h in tap.sealed)
    worst("sealed_gap", abs(sealed - tap.events) / max(tap.events, 1),
          limits["sealed_gap"])
    worst("seal_failures", float(seal_failures), 0.0)

    exact = Exact(tap.keys[:tap.events], geometry["entropy-log2-width"])
    rng = np.random.default_rng(seed)
    pick = set(rng.choice(len(inside), size=min(ANSWERS, len(inside)),
                          replace=False).tolist()) if inside else set()
    pick |= {len(inside) - 1} if inside else set()
    sample = [inside[i] for i in sorted(pick)] + teardown
    for _t, b, s in sample:
        prefix = int(tap.end[min(b, tap.batches - 1)])
        for name, (value, limit) in check_answers(
                s, exact, prefix, geometry, limits).items():
            worst(name, value, limit)
    worst("answers_checked", float(len(sample)), None)
    ok = (len(inside) > 0 and all(
        limit is None or value <= limit for value, limit in rows.values()))
    return ok, {k: {"value": v, "limit": lim} for k, (v, lim) in rows.items()}
