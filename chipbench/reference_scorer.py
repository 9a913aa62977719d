"""The plain reference of the anomaly path: what `advise seccomp-profile`
with `anomaly true` must answer for a recorded stream, computed the long way.

It imports nothing of the program: numpy for the counting, plain
`jax.numpy` in float32 under `jax.default_matmul_precision("highest")` for
the autoencoder. Three answers:

- `histograms`: the exact per-container histogram of the distribution
  column (`key_hash % dim`), as integers.
- `syscall_sets`: the exact per-container set of syscall numbers, by the
  synthetic mode's rule (`aux2 % 335`).
- `Replay`: the autoencoder written out (tanh `gelu`, as `jax.nn.gelu`
  defaults to; mean squared error; `jax.grad`; Adam with optax's constants
  and its bias correction spelt in full), one step a harvest from initial
  weights handed in as arrays, each step on the histograms of the stream so
  far, the scores taken with the weights the step leaves.

Departures from `models/autoencoder.py`, each on purpose:

- every matrix product in float32 at the highest precision; the program
  casts activations and weights to bfloat16 for them and keeps the
  activations in bfloat16 through `gelu` and the bias add. That is the gap
  `TOLERANCE` covers;
- the rows are the containers seen and no others, ascending by mount
  namespace; the program steps on a power of two of rows, the filler rows
  masked out of the loss, in the order the containers appeared. The loss is
  a mean over rows and each score is a row's own, so neither changes the
  answer;
- Adam is written out here; the program's is `optax.adam(1e-3)`.

`Recorder` keeps what a run hands over (the columns of every batch, the
scores and the batch count at every summary) and `compare` holds the run to
the three answers. The benchmark's `correct` does not call this yet (the tap
records neither `mntns` nor `aux2`): the tier-1 tests and `chip_smoke.py`'s
`anomaly` phase do.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

SYSCALLS = 335                    # the gadget's synthetic rule: aux2 % 335
# optax.adam's constants (optax/_src/alias.py: b1, b2, eps; eps_root 0)
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
LEARNING_RATE = 1e-3
# Widest relative gap between a score of the program and the replay's:
# |program - reference| <= TOLERANCE x reference. The gap is the program's
# bfloat16 matrix products and activations against float32 at "highest"
# here (8 bits of mantissa: 2^-9 = 0.2% an element, partly averaged out
# over a row's 4,096 squared errors, partly not: the rounding of one
# weight moves every row alike). Set between two readings (PERF.md section
# 6, PR 31): sound runs read at most 6.5e-3 (CPU tests at 256-256-64 and
# 4096-256-64 over 30-41 harvests; 5.5e-3 on the chip at the
# configuration's sizes), and the least of the three planted faults (a
# training step skipped, the scores taken before the step, the parameters
# rounded to bfloat16 after each step) reads 5.1e-2 to 7.4e-2 after 30-41
# harvests (the third; the first two read over 0.1 from the first steps).
TOLERANCE = 2e-2


def histograms(mntns: np.ndarray, keys: np.ndarray, dim: int
               ) -> tuple[np.ndarray, np.ndarray]:
    """The containers of a stream, ascending, and the exact `[containers,
    dim]` integer histogram of `keys % dim` for each."""
    ids, row = np.unique(mntns, return_inverse=True)
    bucket = (keys.astype(np.uint64) % np.uint64(dim)).astype(np.int64)
    flat = np.bincount(row.astype(np.int64) * dim + bucket,
                       minlength=len(ids) * dim)
    return ids, flat.reshape(len(ids), dim)


def syscall_sets(mntns: np.ndarray, aux2: np.ndarray) -> dict[int, set[int]]:
    """The exact set of syscall numbers of each container."""
    out: dict[int, set[int]] = {}
    pairs = np.unique(np.stack([mntns.astype(np.uint64),
                                aux2.astype(np.uint64) % np.uint64(SYSCALLS)]),
                      axis=1)
    for ns, nr in zip(pairs[0].tolist(), pairs[1].tolist()):
        out.setdefault(ns, set()).add(nr)
    return out


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _forward(params: dict, x):
    h = _gelu(x @ params["enc1"]["w"] + params["enc1"]["b"])
    z = _gelu(h @ params["enc2"]["w"] + params["enc2"]["b"])
    h = _gelu(z @ params["dec1"]["w"] + params["dec1"]["b"])
    return h @ params["dec2"]["w"] + params["dec2"]["b"]


def _loss(params: dict, x):
    return jnp.mean((_forward(params, x) - x) ** 2)


def _normalize(counts: np.ndarray):
    x = jnp.log1p(jnp.asarray(counts, dtype=jnp.float32))
    return x / jnp.maximum(x.sum(axis=-1, keepdims=True), 1e-6)


class Replay:
    """The scorer's state replayed from `params` (a dict of layers `enc1`,
    `enc2`, `dec1`, `dec2`, each `{"w", "b"}` as arrays): `step(counts)`
    is one harvest's work, an Adam step on the normalised rows and then
    each row's score with the new weights."""

    def __init__(self, params: dict):
        self.params = jax.tree.map(
            lambda a: jnp.asarray(np.asarray(a), dtype=jnp.float32), params)
        self.m = jax.tree.map(jnp.zeros_like, self.params)
        self.v = jax.tree.map(jnp.zeros_like, self.params)
        self.t = 0

    def step(self, counts: np.ndarray) -> np.ndarray:
        with jax.default_matmul_precision("highest"):
            x = _normalize(counts)
            grads = jax.grad(_loss)(self.params, x)
            self.t += 1
            self.m = jax.tree.map(
                lambda m, g: ADAM_B1 * m + (1.0 - ADAM_B1) * g, self.m, grads)
            self.v = jax.tree.map(
                lambda v, g: ADAM_B2 * v + (1.0 - ADAM_B2) * g * g,
                self.v, grads)
            c1, c2 = 1.0 - ADAM_B1 ** self.t, 1.0 - ADAM_B2 ** self.t
            self.params = jax.tree.map(
                lambda p, m, v: p - LEARNING_RATE * (m / c1) / (
                    jnp.sqrt(v / c2) + ADAM_EPS),
                self.params, self.m, self.v)
            recon = _forward(self.params, x)
            return np.asarray(
                jnp.mean((recon - x) ** 2, axis=-1) * x.shape[-1])


class Recorder:
    """What a run hands over: `on_batch` keeps the three columns of every
    batch, `on_summary` the scores and how many batches the summary covers.
    A harvest runs at the end of a batch's turn, before the runtime hands
    that batch to `on_batch`, so a summary covers one batch more than were
    seen when it came; the teardown harvest covers them all."""

    def __init__(self):
        self.mntns: list[np.ndarray] = []
        self.keys: list[np.ndarray] = []
        self.aux2: list[np.ndarray] = []
        self.summaries: list[tuple[int, dict[int, float]]] = []

    def on_batch(self, batch) -> None:
        n = batch.count
        self.mntns.append(batch.cols["mntns"][:n].copy())
        self.keys.append(batch.cols["key_hash"][:n].copy())
        self.aux2.append(batch.cols["aux2"][:n].copy())

    def on_summary(self, summary) -> None:
        self.summaries.append((len(self.mntns) + 1,
                               dict(summary.anomaly or {})))


def compare(rec: Recorder, params: dict, dim: int,
            profile: dict[int, set[int]] | None = None,
            counts: tuple[list[int], np.ndarray] | None = None,
            summaries: list[int] | None = None) -> dict:
    """A recorded run against the three answers. `params` are the scorer's
    initial weights, `profile` the syscall numbers the run recorded for each
    container, `counts` the program's own `(containers, [rows, dim])`
    histograms after the last batch, `summaries` the indices of the
    summaries whose scores are compared (all of them if None; the replay
    steps through every one either way). Returns the readings: the widest
    relative score gap, and whether keys, histograms and profile are
    exact."""
    batches = len(rec.mntns)
    ids = np.unique(np.concatenate(rec.mntns)) if batches else np.zeros(
        0, np.uint64)
    total = np.zeros((len(ids), dim), dtype=np.int64)
    seen = np.zeros(len(ids), dtype=bool)
    replay = Replay(params)
    judged = set(range(len(rec.summaries)) if summaries is None
                 else summaries)
    done = 0
    worst, keys_equal, scored = 0.0, True, 0
    for i, (covers, scores) in enumerate(rec.summaries):
        covers = min(covers, batches)
        if covers > done:
            mntns = np.concatenate(rec.mntns[done:covers])
            part_ids, part = histograms(
                mntns, np.concatenate(rec.keys[done:covers]), dim)
            rows = np.searchsorted(ids, part_ids)
            total[rows] += part
            seen[rows] = True
            done = covers
        if not seen.any():
            keys_equal &= not scores
            continue
        want = replay.step(total[seen])
        if i not in judged:
            continue
        want_ids = ids[seen].tolist()
        keys_equal &= sorted(scores) == want_ids
        for ns, ref in zip(want_ids, want.tolist()):
            if ns in scores:
                worst = max(worst, abs(scores[ns] - ref) / ref)
                scored += 1
    out = {"score_gap": worst, "scores_compared": scored,
           "score_keys_equal": bool(keys_equal), "harvests": replay.t}
    if counts is not None:
        got_ids, got = counts
        order = np.argsort(np.asarray(got_ids, dtype=np.uint64))
        out["histograms_exact"] = bool(
            done == batches
            and np.array_equal(np.asarray(got_ids, np.uint64)[order], ids)
            and np.array_equal(np.asarray(got)[order].astype(np.int64),
                               total))
    if profile is not None:
        out["profile_exact"] = profile == (syscall_sets(
            np.concatenate(rec.mntns), np.concatenate(rec.aux2))
            if batches else {})
    return out
