"""The host tap: what the benchmark records while a gadget run is going.

It sits on the three callbacks LocalRuntime.run_gadget offers (`on_batch`,
`on_sketch_summary`, `on_window_sealed`) and does as little as it can inside
the run: per batch one fold of the key column into a buffer allocated in
set-up, and four scalars. Everything derived from them (exact counts, rates,
lags) is computed after the window has closed.

Window: it opens at the first summary of the measured run (the state is
warm, the loop is in its stride) and closes `seconds` later, at the first
batch past the deadline, where the tap calls `cancel`.

Lag pairing: `harvest` is called at the end of `enrich_batch(batch k)` and
blocks on a digest that contains batch k; the tap for batch k fires right
after. So a summary's lag is its callback's wall time minus the newest
creation stamp (`ts`, CLOCK_REALTIME ns) of the batch whose tap fires next.
"""

from __future__ import annotations

import time
from typing import Callable

import numpy as np


class Tap:
    def __init__(self, *, seconds: float, capacity_events: int,
                 capacity_batches: int, cancel: Callable[[], None],
                 snapshot: Callable[[], dict],
                 trace_last_s: float = 0.0,
                 start_trace: Callable[[], None] | None = None):
        self.seconds = seconds
        self.cancel = cancel
        self.snapshot = snapshot
        self.trace_last_s = trace_last_s
        self.start_trace = start_trace
        # touched here, in set-up, so the window takes no page faults
        self.keys = np.zeros(capacity_events, np.uint32)
        self._tmp = np.zeros(1 << 20, np.uint64)
        self.end = np.zeros(capacity_batches, np.int64)      # events so far
        self.drops = np.zeros(capacity_batches, np.int64)    # source, cumulative
        self.newest_ns = np.zeros(capacity_batches, np.uint64)
        self.batches = 0
        self.events = 0
        # (wall time of the callback, index of the batch it closes, summary)
        self.summaries: list[tuple[float, int, object]] = []
        self.sealed: list[tuple[float, dict]] = []
        self.window_start: float | None = None
        self.window_end: float | None = None
        self.trace_start: float | None = None
        self.snap_start: dict | None = None
        self.snap_end: dict | None = None      # at trace start when traced
        self.first_batch = 0                   # first batch inside the window
        self.last_batch = 0                    # one past the last
        self.counters_batch = 0                # one past the last the counters saw
        self.counters_end: float | None = None # when the counters were read
        self.overflow: str | None = None

    # -- callbacks ---------------------------------------------------------

    def on_summary(self, s) -> None:
        now = time.time()
        self.summaries.append((now, self.batches, s))
        if self.window_start is None:
            self.window_start = now
            self.snap_start = self.snapshot()
            self.first_batch = self.batches + 1

    def on_sealed(self, header: dict) -> None:
        self.sealed.append((time.time(), header))

    def on_batch(self, batch) -> None:
        n = batch.count
        i, at = self.batches, self.events
        if i >= len(self.end) or at + n > len(self.keys):
            # cannot record: the reference would be short. Stop the run and
            # let the harness fail loudly instead of checking a part of it
            self.overflow = (f"tap full after {i} batches / {at} events")
            self.cancel()
            return
        k = batch.cols["key_hash"][:n]
        tmp = self._tmp[:n]
        np.right_shift(k, np.uint64(32), out=tmp)
        np.bitwise_xor(tmp, k, out=tmp)
        self.keys[at:at + n] = tmp          # keeps the low word: hi ^ lo
        self.end[i] = at + n
        self.drops[i] = batch.drops
        self.newest_ns[i] = batch.cols["ts"][:n].max()
        now = time.time()
        self.batches, self.events = i + 1, at + n
        if self.window_start is None or self.window_end is not None:
            return
        left = self.window_start + self.seconds - now
        if left <= 0:
            self.window_end = now
            self.last_batch = self.batches
            if self.snap_end is None:
                self._read_counters(now)
            self.cancel()
        elif (self.start_trace is not None and self.trace_start is None
              and left <= self.trace_last_s):
            # counters stop here: the profiler slows the host, so what the
            # registry counted is read over the untraced part of the window
            self._read_counters(now)
            self.start_trace()
            self.trace_start = time.time()

    def _read_counters(self, now: float) -> None:
        self.snap_end = self.snapshot()
        self.counters_batch = self.batches
        self.counters_end = now

    # -- after the window --------------------------------------------------

    def absorbed(self, first: int, last: int) -> int:
        """Events in batches [first, last)."""
        lo = self.end[first - 1] if first > 0 else 0
        hi = self.end[last - 1] if last > 0 else 0
        return int(hi - lo)

    def shed(self, first: int, last: int) -> int:
        """Events the source's ring dropped while batches [first, last) were
        popped (the batch before `first` closes the interval before it)."""
        if last <= first:
            return 0
        return int(self.drops[last - 1] - self.drops[max(first - 1, 0)])

    def window_summaries(self) -> list[tuple[float, int, object]]:
        """Summaries emitted inside the window (the one that opened it and
        the teardown harvest are not)."""
        return [(t, b, s) for t, b, s in self.summaries
                if self.first_batch <= b < self.last_batch]

    def lags_ms(self) -> np.ndarray:
        return np.array([(t - float(self.newest_ns[b]) / 1e9) * 1e3
                         for t, b, _s in self.window_summaries()])
