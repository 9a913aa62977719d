"""Pure-Python synthetic source — same interface as NativeCapture.

The no-toolchain fallback (the role pkg/standardgadgets plays for the
reference when CO-RE/BTF is unavailable: same events, slower path,
standardtracerbase.go:40-81). Deterministic per seed; numpy-vectorized.
"""

from __future__ import annotations

import time

import numpy as np

from ..columns.columns import fnv1a64
from .batch import EventBatch


class PySyntheticSource:
    def __init__(self, kind: int = 1, *, seed: int = 0, vocab: int = 1000,
                 zipf_s: float = 1.2, batch_size: int = 8192,
                 containers: int = 64, prob_delayed_event: float = 0.0,
                 occasional_delay: float = 0.0):
        self.kind = kind
        self._containers = containers
        self._max_delay_ns = round(occasional_delay * 1e9)
        self._prob_delayed = prob_delayed_event if self._max_delay_ns else 0.0
        self.batch_size = batch_size
        self._rng = np.random.default_rng(seed or 42)
        self._names = [f"proc-{i}" for i in range(vocab)]
        self._hashes = np.array([fnv1a64(n) for n in self._names], dtype=np.uint64)
        # zipf pmf over a finite vocab
        ranks = np.arange(1, vocab + 1, dtype=np.float64)
        pmf = ranks ** (-zipf_s)
        self._pmf = pmf / pmf.sum()
        self._vocab = {int(h): n for h, n in zip(self._hashes, self._names)}
        self._seq = 0

    def start(self) -> None:  # interface parity
        pass

    def stop(self) -> None:
        pass

    def close(self) -> None:
        pass

    def generate(self, n: int | None = None) -> EventBatch:
        n = n or self.batch_size
        idx = self._rng.choice(len(self._pmf), size=n, p=self._pmf)
        b = EventBatch.alloc(n, with_comm=False)
        b.cols["ts"][:] = time.time_ns()
        b.cols["key_hash"][:] = self._hashes[idx]
        b.cols["mntns"][:] = np.uint64(4026531840) + (idx % self._containers).astype(np.uint64)
        b.cols["pid"][:] = self._rng.integers(1000, 51000, n, dtype=np.uint32)
        b.cols["uid"][:] = self._rng.integers(0, 4, n, dtype=np.uint32)
        b.cols["kind"][:] = self.kind
        b.cols["aux1"][:] = self._rng.integers(0, 2**63, n, dtype=np.uint64)
        b.cols["aux2"][:] = self._rng.integers(0, 2**16, n, dtype=np.uint64)
        if self._prob_delayed > 0.0:
            # the native source's delayed events: with probability p an
            # event time older by d, d uniform on (0, occasional-delay]
            late = self._rng.random(n) < self._prob_delayed
            d = self._rng.integers(1, self._max_delay_ns + 1, n,
                                   dtype=np.uint64)
            b.cols["ts"][late] -= d[late]
        b.count = n
        b.seq = self._seq
        self._seq += n
        # pipeline-health watermarks: synthesis IS the pop, so both
        # stamps land on the same clock read (host lag 0 by definition —
        # the device-lag watermark downstream stays meaningful)
        b.pop_ts = b.oldest_ts = time.time()
        return b

    pop = generate

    def drops(self) -> int:
        return 0

    def vocab_lookup(self, key_hash: int) -> str:
        return self._vocab.get(int(key_hash), "")
