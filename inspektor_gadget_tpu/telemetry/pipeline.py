"""Pipeline health plane: per-stage lag watermarks, backpressure and
starvation accounting for the ingest hot path.

The BENCH_r04 starvation gap (device plane eats 2.6B ev/s/chip, one host
thread supplies 130M) was only visible in one-off bench sessions;
a live fleet had no per-stage lag, occupancy, or starvation signal at
all. This module is the standing instrument: every tpusketch run
registers a `PipelineStats`, the staging layer and the
operator ingest loop feed it batch-grain observations, and every surface
the fleet already looks at — harvest summaries, DumpState, Prometheus,
doctor, `ig-tpu fleet lag`, the `pipeline_lag` alert kind — reads its
`snapshot()`.

Vocabulary (docs/observability.md "Pipeline health & backpressure"):

- **Watermark**: each batch carries its oldest-event timestamp and its
  pop timestamp (sources/batch.py `oldest_ts`/`pop_ts`, stamped once per
  batch — zero per-event cost). Host lag = pop − oldest event; device
  lag = dispatch − pop. The *watermark* of a stage is the lag of the
  most recently dispatched batch.
- **Starved tick**: the H2D stager found its next ring slot empty — the
  device had already drained everything in flight; the host is the
  bottleneck (the BENCH_r04 regime).
- **Saturated tick**: the slot was still occupied — the host is a full
  ring depth ahead and must block on `block_until_ready` (the stall
  seconds are measured); the device is the bottleneck.
- **starved_ratio** = starved / (starved + saturated).

- **Turn**: one pass of a run's loop that popped a batch, from the
  previous turn's end to this one's. The loop thread times each stage
  of it (`TurnClock.stage`, `TURN_STAGES`) and publishes the turn once,
  after the batch handler returned; the four longest turns are kept
  with their stage split and the loop thread's CPU time.
- **Harvest interval**: the loop's wall from one harvest to the next,
  closed by the loop thread inside the harvest (`TurnClock.
  close_interval`) and split into who held the loop: `cpu` (the thread
  ran), `runqueue` (runnable, no core: schedstat's run delay), `wait`
  (off the CPU in a timed wait: `source_wait`, `harvest_wait`, the
  stager's retire, the seal drain's join) and `blocked` (the rest: the
  interpreter lock, other locks, page faults). A gap of three or more
  intervals counts its missing summaries, by the benchmark's own rule,
  with a cause; the four longest intervals are kept.

Lag *distributions* eat the quantile plane's own dogfood: each stage
feeds a host-side DDSketch twin (`LagSketch`, same bucket math as
`ops/quantiles.py`, pure numpy — this module must not import jax) so
summaries carry p50/p99 lag per stage, not just the last watermark.
"""

from __future__ import annotations

import gc
import math
import threading
import time

import numpy as np

from ..utils.compile_cache import BACKEND_COMPILES
from .registry import counter, gauge
from .tracing import annotation_class

_tm_stage_lag = gauge(
    "ig_pipeline_stage_lag_seconds",
    "Lag watermark of the most recent batch through a pipeline stage",
    ("stage", "lane"))
_tm_starved_ratio = gauge(
    "ig_pipeline_starved_ratio",
    "starved / (starved + saturated) stager ticks — 1.0 means the device "
    "always drained the ring before the host refilled it (host-bound)")
_tm_backpressure = counter(
    "ig_pipeline_backpressure_total",
    "Ticks a pipeline stage blocked on a full downstream ring",
    ("stage",))
_tm_occupancy = gauge(
    "ig_pipeline_occupancy",
    "Occupied slots in a pipeline stage's ring",
    ("stage", "lane"))

# the stages of a turn, in the order of the turn; each is also the host
# annotation `ig:<name>` on the profiler's clock (siblings, never nested)
TURN_STAGES = (
    "source_wait", "source_pop", "source_filter", "gadget_record",
    "operator_other", "tpusketch_fold", "tpusketch_window_route",
    "tpusketch_h2d", "tpusketch_shard_restage", "tpusketch_update",
    "tpusketch_window_planes",
    "tpusketch_slices", "tpusketch_inv_classes", "tpusketch_post",
    "tpusketch_container_dists", "tpusketch_seal", "tpusketch_shard_merge",
    "tpusketch_harvest", "runtime_deliver")
# stages only a run under shard-ingest opens. A one-chip run carries none
# of their names: no counter child, no key in the `pipeline` block
SHARD_STAGES = ("tpusketch_shard_restage", "tpusketch_shard_merge")
# likewise: the gadget's own `process_batch`, which only a gadget that
# overrides it has (taken out of source_filter), and the per-container
# distributions of a run with the anomaly scorer on (out of tpusketch_post)
RECORD_STAGE = "gadget_record"
DISTS_STAGE = "tpusketch_container_dists"
OPTIONAL_STAGES = SHARD_STAGES + (RECORD_STAGE, DISTS_STAGE)
# parts of tpusketch_harvest counted apart: they ride the same array but
# are no stages (they tile nothing). The blocking read of the digest; and,
# only with the anomaly scorer on, the scorer's dispatch, put and read-back
HARVEST_WAIT = "harvest_wait"
ANOMALY_SCORE = "anomaly_score"
_TURN_SLOTS = TURN_STAGES + (HARVEST_WAIT, ANOMALY_SCORE)
_I_WAIT = _TURN_SLOTS.index(HARVEST_WAIT)
_I_SCORE = _TURN_SLOTS.index(ANOMALY_SCORE)
SLOW_TURNS = 4    # longest turns of a run kept with their stage split
# stages that are timed waits too (their off-CPU part is the harvest
# interval's `wait`)
WAIT_STAGES = ("source_wait",)

_tm_turn_seconds = counter(
    "ig_pipeline_turn_seconds_total",
    "Loop-thread seconds per stage of the batch turn (harvest_wait, the "
    "blocking digest read, and anomaly_score, the scorer's dispatch and "
    "read-back, are parts of tpusketch_harvest)", ("stage",))
_tm_turns = counter(
    "ig_pipeline_turns_total", "Batch turns a gadget run's loop published")

# who held the loop between two harvests (TurnClock.close_interval), in
# the order a part is taken out of the interval's wall; the causes of a
# late summary, in the order they are decided
LOOP_PARTS = ("cpu", "runqueue", "wait", "blocked")
LATE_CAUSES = ("gc", "compile") + LOOP_PARTS
HARVEST_GAPS = 4  # longest harvest intervals of a run kept with their split
# field 2 is the thread's time runnable on a run queue, in ns; a kernel
# without schedstats has no file or reads 0
SCHEDSTAT = "/proc/thread-self/schedstat"

_tm_loop_seconds = counter(
    "ig_pipeline_loop_seconds_total",
    "Loop-thread wall seconds from one harvest to the next, by who held "
    "the loop: cpu (the thread ran), runqueue (runnable without a core), "
    "wait (off the CPU in a timed wait), blocked (the rest: the "
    "interpreter lock, other locks, page faults)", ("part",))
_tm_loop_intervals = counter(
    "ig_pipeline_loop_intervals_total",
    "Harvest intervals a gadget run's loop thread closed")
_tm_gc_seconds = counter(
    "ig_runtime_gc_seconds_total",
    "Seconds inside the cyclic garbage collector, on any thread",
    ("generation",))
_tm_gc_collections = counter(
    "ig_runtime_gc_collections_total",
    "Collections of the cyclic garbage collector", ("generation",))


class LagSketch:
    """Host-twin DDSketch over a single stage's lag samples.

    Same bucket geometry as ops/quantiles.py `dd_init` defaults (alpha
    1%, 2048 buckets, min_value 1e-9 — spans ns..~30s), replicated in
    scalar math because telemetry must stay importable without jax;
    tests/test_pipeline_health.py pins parity against `dd_quantile_np`.
    One sample per *batch*, so the per-add cost is a log and an int
    increment, nothing per event.
    """

    __slots__ = ("alpha", "min_value", "counts", "zeros", "total",
                 "watermark", "_inv_log_gamma", "_offset", "_gamma")

    def __init__(self, alpha: float = 0.01, n_buckets: int = 2048,
                 min_value: float = 1e-9):
        self.alpha = alpha
        self.min_value = min_value
        self._gamma = (1.0 + alpha) / (1.0 - alpha)
        self._inv_log_gamma = 1.0 / math.log(self._gamma)
        self._offset = math.log(min_value) * self._inv_log_gamma
        self.counts = np.zeros(n_buckets, np.int64)
        self.zeros = 0
        self.total = 0
        self.watermark = 0.0

    def add(self, v: float) -> None:
        self.watermark = float(v)
        self.total += 1
        if v <= 0.0:
            self.zeros += 1
            return
        idx = math.ceil(math.log(max(v, self.min_value))
                        * self._inv_log_gamma - self._offset)
        self.counts[min(max(idx, 0), len(self.counts) - 1)] += 1

    def quantile(self, q: float) -> float:
        """Value at quantile q — the dd_quantile_np formula on this
        sketch's own lanes (0.0 inside the zero bucket / empty sketch:
        a lag gauge must never surface NaN)."""
        if self.total <= 0:
            return 0.0
        rank = q * max(self.total - 1.0, 0.0)
        if rank < self.zeros:
            return 0.0
        cum = self.zeros + np.cumsum(self.counts.astype(np.float64))
        bucket = int((cum <= rank).sum())
        bucket = min(bucket, len(self.counts) - 1)
        log_gamma = math.log(self._gamma)
        offset = math.log(self.min_value) / log_gamma
        return float(2.0 * math.exp((bucket + offset) * log_gamma)
                     / (self._gamma + 1.0))


class PipelineStats:
    """Per-run pipeline health accounting, fed batch-grain from the
    staging layer (starved/saturated/stall/occupancy) and the operator
    ingest loop (watermarks) — registered like SketchStatsSource so live
    surfaces (DumpState, doctor, fleet lag) can find it by run."""

    def __init__(self, run_id: str, gadget: str = ""):
        self.run_id = run_id
        self.gadget = gadget
        self._mu = threading.Lock()
        self._stages: dict[tuple[str, int], LagSketch] = {}
        self.starved = 0
        self.saturated = 0
        self.stall_s = 0.0
        self.rounds = 0
        # sharded ingest only (shard_lanes): events parked per lane, and
        # the rounds a harvest, seal or checkpoint closed early with
        # their zero-weight filler lanes
        self._lane_events: list[int] | None = None
        self._rounds_flushed = 0
        self._filler_lanes = 0
        self._backpressure: dict[str, int] = {}
        self._occupancy: dict[str, float] = {}
        self._occ_touched: set[tuple[str, str]] = set()
        # turn accounting (TurnClock.publish): run totals in ns per slot
        # of _TURN_SLOTS, and the SLOW_TURNS longest turns, longest first
        self._turns = 0
        self._turn_wall_ns = 0
        self._turn_ns = [0] * len(_TURN_SLOTS)
        # the OPTIONAL_STAGES this run opened (TurnClock.attach shares the
        # clock's set): only they get a key in the `turn` block
        self._opened: set[str] = set()
        self._slow: list[tuple[int, int, float, int, tuple[int, ...]]] = []
        self._slow_floor = 0   # a turn must outlast this to enter _slow
        # harvest intervals (TurnClock.close_interval): run totals in
        # seconds per part of LOOP_PARTS (runqueue None once any interval
        # could not read it), collections, late summaries by cause,
        # intervals that shed, and the HARVEST_GAPS longest records,
        # longest first
        self._intervals = 0
        self._iv_wall_s = 0.0
        self._iv_parts: dict[str, float | None] = dict.fromkeys(LOOP_PARTS,
                                                               0.0)
        self._iv_gc_s = 0.0
        self._late: dict[str, int] = {}
        self._shed_intervals = 0
        self._gaps: list[dict] = []

    # -- observations (hot path: one lock + O(1) work per batch) ------------

    def note_lag(self, stage: str, lag_s: float, lane: int = 0) -> None:
        lag_s = max(float(lag_s), 0.0)
        with self._mu:
            sk = self._stages.get((stage, lane))
            if sk is None:
                sk = self._stages[(stage, lane)] = LagSketch()
            sk.add(lag_s)
        _tm_stage_lag.labels(stage=stage, lane=str(lane)).set(lag_s)

    def note_host_lag(self, lag_s: float, lane: int = 0) -> None:
        """pop − oldest event: how stale a batch already was when the
        host popped it off the capture ring."""
        self.note_lag("pop", lag_s, lane)

    def note_device_lag(self, lag_s: float, lane: int = 0) -> None:
        """dispatch − pop: how long a popped batch waited for staging +
        the device update to pick it up."""
        self.note_lag("h2d", lag_s, lane)

    def note_starved(self, lane: int = 0) -> None:
        with self._mu:
            self.starved += 1
            ratio = self.starved / (self.starved + self.saturated)
        _tm_starved_ratio.set(ratio)

    def note_saturated(self, stall_s: float, lane: int = 0,
                       stage: str = "h2d") -> None:
        with self._mu:
            self.saturated += 1
            self.stall_s += max(float(stall_s), 0.0)
            self._backpressure[stage] = self._backpressure.get(stage, 0) + 1
            ratio = self.starved / (self.starved + self.saturated)
        _tm_starved_ratio.set(ratio)
        _tm_backpressure.labels(stage=stage).inc()

    def note_backpressure(self, stage: str, n: int = 1) -> None:
        with self._mu:
            self._backpressure[stage] = self._backpressure.get(stage, 0) + n
        _tm_backpressure.labels(stage=stage).inc(n)

    def note_occupancy(self, stage: str, occupied: float,
                       lane: int = 0) -> None:
        with self._mu:
            self._occupancy[f"{stage}:{lane}"] = float(occupied)
            self._occ_touched.add((stage, str(lane)))
        _tm_occupancy.labels(stage=stage, lane=str(lane)).set(occupied)

    def shard_lanes(self, chips: int) -> None:
        """Sharded ingest is on: the snapshot also carries the `shard`
        block and the stages of SHARD_STAGES."""
        with self._mu:
            self._lane_events = [0] * chips
            self._opened.update(SHARD_STAGES)

    def note_lane_events(self, lane: int, events: int) -> None:
        with self._mu:
            self._lane_events[lane] += events

    def note_round(self, fillers: int = 0) -> None:
        """One dispatched round; `fillers` lanes of it held no batch (a
        flush closed the round early)."""
        with self._mu:
            self.rounds += 1
            if fillers:
                self._rounds_flushed += 1
                self._filler_lanes += fillers

    def note_turn(self, ns: list[int], wall_ns: int, cpu_ns: int,
                  start: float, seq: int) -> None:
        """One published turn: `ns` per slot of _TURN_SLOTS (the caller's
        live array: copied only when the turn enters the longest four),
        its wall and loop-thread CPU time, wall-clock start and batch
        sequence number."""
        with self._mu:
            self._turns += 1
            self._turn_wall_ns += wall_ns
            tot = self._turn_ns
            for i, v in enumerate(ns):
                tot[i] += v
            if wall_ns > self._slow_floor:
                slow = self._slow
                slow.append((wall_ns, cpu_ns, start, seq, tuple(ns)))
                slow.sort(reverse=True)
                del slow[SLOW_TURNS:]
                if len(slow) == SLOW_TURNS:
                    self._slow_floor = slow[-1][0]

    def note_interval(self, rec: dict) -> None:
        """One closed harvest interval, as `harvest_gaps` keeps it."""
        with self._mu:
            self._intervals += 1
            self._iv_wall_s += rec["wall_s"]
            tot = self._iv_parts
            for part, v in rec["parts"].items():
                tot[part] = None if v is None or tot[part] is None \
                    else tot[part] + v
            self._iv_gc_s += rec["gc_s"]
            if rec["missing"]:
                self._late[rec["cause"]] = (self._late.get(rec["cause"], 0)
                                            + rec["missing"])
            if rec["shed_events"]:
                self._shed_intervals += 1
            gaps = self._gaps
            if len(gaps) < HARVEST_GAPS or rec["wall_s"] > gaps[-1]["wall_s"]:
                gaps.append(rec)
                gaps.sort(key=lambda r: r["wall_s"], reverse=True)
                del gaps[HARVEST_GAPS:]

    # -- reads --------------------------------------------------------------

    def snapshot(self) -> dict:
        """The `pipeline` block harvest summaries / DumpState carry —
        plain JSON-able dict, stable keys (alert summary_fields and the
        fleet lag table key into it)."""
        with self._mu:
            stages: dict[str, dict] = {}
            for (stage, lane), sk in sorted(self._stages.items()):
                row = stages.setdefault(stage, {
                    "watermark_s": 0.0, "p50_s": 0.0, "p99_s": 0.0,
                    "count": 0})
                # multi-lane stages report the worst lane's view: the
                # fleet cares about the laggiest lane, not the average
                row["watermark_s"] = max(row["watermark_s"], sk.watermark)
                row["p50_s"] = max(row["p50_s"], sk.quantile(0.50))
                row["p99_s"] = max(row["p99_s"], sk.quantile(0.99))
                row["count"] += sk.total
            ticks = self.starved + self.saturated
            sharded = self._lane_events is not None
            shard = {"shard": {
                "rounds_full": self.rounds - self._rounds_flushed,
                "rounds_flushed": self._rounds_flushed,
                "filler_lanes": self._filler_lanes,
                "lane_events": list(self._lane_events)}} if sharded else {}
            opened = self._opened
            return {
                "stages": stages,
                "host_lag_s": stages.get("pop", {}).get("watermark_s", 0.0),
                "device_lag_s": stages.get("h2d", {}).get("watermark_s", 0.0),
                "starved": self.starved,
                "saturated": self.saturated,
                "starved_ratio": (self.starved / ticks) if ticks else 0.0,
                "stall_s": self.stall_s,
                "backpressure": dict(self._backpressure),
                "occupancy": dict(self._occupancy),
                "rounds": self.rounds,
                **shard,
                "turn": {
                    "turns": self._turns,
                    "wall_s": self._turn_wall_ns * 1e-9,
                    "stages": {n: v * 1e-9 for n, v in
                               zip(TURN_STAGES, self._turn_ns)
                               if n not in OPTIONAL_STAGES or n in opened},
                    "harvest_wait_s": self._turn_ns[_I_WAIT] * 1e-9,
                    **({"anomaly_score_s": self._turn_ns[_I_SCORE] * 1e-9}
                       if ANOMALY_SCORE in opened else {}),
                },
                "slow_turns": [
                    {"wall_s": wall * 1e-9, "cpu_s": cpu * 1e-9,
                     "start": start, "seq": seq,
                     "stages": {n: v * 1e-9 for n, v in
                                zip(_TURN_SLOTS, ns) if v}}
                    for wall, cpu, start, seq, ns in self._slow],
                "loop": {
                    "intervals": self._intervals,
                    "wall_s": self._iv_wall_s,
                    "parts": dict(self._iv_parts),
                    "gc_s": self._iv_gc_s,
                    "summaries_late": dict(self._late),
                    "shed_intervals": self._shed_intervals,
                },
                "harvest_gaps": [dict(r, parts=dict(r["parts"]))
                                 for r in self._gaps],
            }

    # -- lifecycle ----------------------------------------------------------

    def register(self) -> None:
        with _live_mu:
            _live[self.run_id] = self

    def unregister(self) -> None:
        """Drop out of the live registry and return every gauge this run
        touched exactly to baseline (the PR-9/PR-11 teardown-accounting
        discipline: a stopped run leaves no residue on shared gauges)."""
        with _live_mu:
            _live.pop(self.run_id, None)
        with self._mu:
            touched = list(self._stages.keys())
            occ = list(self._occ_touched)
        for stage, lane in touched:
            _tm_stage_lag.labels(stage=stage, lane=str(lane)).set(0.0)
        for stage, lane in occ:
            _tm_occupancy.labels(stage=stage, lane=lane).set(0.0)
        _tm_starved_ratio.set(0.0)


class _Stage:
    """One stage of one run's turn as a reusable context manager: two
    clock reads, one add into the turn's array, and the stage's host
    annotation on the profiler's clock. Loop thread only, no lock;
    stages are siblings, so one object per stage is never re-entered."""

    __slots__ = ("_ns", "_i", "_label", "_t0", "_ann")

    def __init__(self, ns: list[int], i: int, label: str):
        self._ns = ns
        self._i = i
        self._label = label

    def __enter__(self) -> None:
        self._t0 = time.perf_counter_ns()
        # an annotation starts when it is made; outside a profiler
        # session none is made (the check costs a tenth of making one)
        cls = annotation_class()
        self._ann = cls(self._label) if cls.is_enabled() else None

    def __exit__(self, _type, _exc, _tb) -> None:
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        self._ns[self._i] += time.perf_counter_ns() - self._t0


class _Wait:
    """A timed wait of the loop thread (the digest's read, the stager's
    retire, the seal drain's join, `source_wait`): its off-CPU part, the
    wall less the thread's CPU time inside it, goes to the harvest
    interval's `wait`, and with a slot its wall to that slot of the turn
    too. Reusable, never re-entered; on another thread it counts
    nothing."""

    __slots__ = ("_clock", "_slot", "_t0", "_c0")

    def __init__(self, clock: "TurnClock", slot: int | None):
        self._clock = clock
        self._slot = slot

    def __enter__(self) -> None:
        if threading.get_ident() == self._clock._loop_ident:
            self._c0 = time.thread_time_ns()
            self._t0 = time.perf_counter_ns()

    def __exit__(self, _type, _exc, _tb) -> None:
        clock = self._clock
        if threading.get_ident() != clock._loop_ident:
            return
        wall = time.perf_counter_ns() - self._t0
        if self._slot is not None:
            clock._ns[self._slot] += wall
        clock._wait_ns += max(wall - (time.thread_time_ns() - self._c0), 0)


class _WaitStage(_Stage):
    """`source_wait`: a stage that is a timed wait too."""

    __slots__ = ("_wait",)

    def __init__(self, ns: list[int], i: int, label: str, wait: _Wait):
        super().__init__(ns, i, label)
        self._wait = wait

    def __enter__(self) -> None:
        self._wait.__enter__()
        _Stage.__enter__(self)

    def __exit__(self, _type, _exc, _tb) -> None:
        _Stage.__exit__(self, _type, _exc, _tb)
        self._wait.__exit__(_type, _exc, _tb)


def run_delay_ns() -> int | None:
    """The calling thread's run-queue delay so far (schedstat field 2),
    or None where the kernel gives none."""
    try:
        with open(SCHEDSTAT, "rb") as f:
            return int(f.read().split()[1]) or None
    except (OSError, IndexError, ValueError):
        return None


class _Collections:
    """Every collection of the process, timed by `gc.callbacks` on
    whatever thread runs it (it holds the interpreter lock either way):
    seconds and counts by generation into the registry, the running
    total the harvest intervals take their `gc_s` from, and `gc:gen<N>`
    on the profiler's clock while a session is on. One a process
    (`time_collections`); a collection never nests in another."""

    def __init__(self):
        self.ns = 0
        self._t0 = 0
        self._ann = None
        self._seconds = [_tm_gc_seconds.labels(generation=str(g))
                         for g in range(3)]
        self._count = [_tm_gc_collections.labels(generation=str(g))
                       for g in range(3)]

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            cls = annotation_class()
            self._ann = (cls(f"gc:gen{info['generation']}")
                         if cls.is_enabled() else None)
            self._t0 = time.perf_counter_ns()
            return
        dt = time.perf_counter_ns() - self._t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        self.ns += dt
        g = info["generation"]
        self._seconds[g].inc(dt * 1e-9)
        self._count[g].inc()


_gc_mu = threading.Lock()
_collections: _Collections | None = None


def time_collections() -> _Collections:
    """The process's collection timer, registered with `gc.callbacks` at
    the first call (and again should anyone have taken it out)."""
    global _collections
    with _gc_mu:
        if _collections is None:
            annotation_class()       # resolved here, not inside a collection
            _collections = _Collections()
        if _collections not in gc.callbacks:
            gc.callbacks.append(_collections)
        return _collections


class TurnClock:
    """Turn accounting of ONE gadget run, made with the run's
    GadgetContext: the source gadget and the operators of the run time
    their stages into the same array, on the loop thread, without a
    lock; `publish()` closes the turn after the batch handler returned.
    Nothing here reaches the tracer's ring."""

    def __init__(self):
        self._ns = [0] * len(_TURN_SLOTS)
        self._loop_ident = threading.get_ident()
        self._wait_ns = 0     # off-CPU ns of timed waits this interval
        self._stages = {name: _WaitStage(self._ns, i, "ig:" + name,
                                         _Wait(self, None))
                        if name in WAIT_STAGES
                        else _Stage(self._ns, i, "ig:" + name)
                        for i, name in enumerate(TURN_STAGES)}
        # stage ns of the turns published in this harvest interval, less
        # the part of the turn the last interval closed in that it counted
        self._iv_ns = [0] * len(_TURN_SLOTS)
        self._gc = time_collections()
        self._parts = {p: None if p == "runqueue"
                       else _tm_loop_seconds.labels(part=p)
                       for p in LOOP_PARTS}
        # an optional stage gets its counter child with its first
        # nanosecond, so the registry of a run that never opens it has no
        # such label
        self._children = [None if name in OPTIONAL_STAGES + (ANOMALY_SCORE,)
                          else _tm_turn_seconds.labels(stage=name)
                          for name in _TURN_SLOTS]
        self._opened: set[str] = set()
        self._stats: PipelineStats | None = None
        self._seq = 0
        self.begin()

    def attach(self, stats: PipelineStats) -> None:
        """Published turns also go to `stats` (the run's `pipeline`
        block: run totals and the longest turns)."""
        stats._opened |= self._opened
        self._opened = stats._opened
        self._stats = stats

    def stage(self, name: str) -> _Stage:
        """The context manager of a stage of TURN_STAGES; bind it once,
        enter it every turn."""
        return self._stages[name]

    def open_stages(self, *names: str) -> None:
        """The run will time these OPTIONAL_STAGES (or ANOMALY_SCORE):
        they get their keys in the `pipeline` block from now on."""
        self._opened.update(names)

    def wait(self, slot: str | None = None) -> _Wait:
        """A timed wait's context manager (loop thread only: elsewhere it
        counts nothing); with `slot` (HARVEST_WAIT) its wall is also
        that counted part of the turn. Bind it once, enter it at each
        wait."""
        return _Wait(self, None if slot is None else _TURN_SLOTS.index(slot))

    def note_anomaly_score(self, ns: int) -> None:
        self._ns[_I_SCORE] += ns

    def begin(self) -> None:
        """The loop starts here: the first turn's wall runs from now."""
        self._ns[:] = [0] * len(self._ns)
        self._t_pub = time.perf_counter_ns()
        self._cpu_pub = time.thread_time_ns()
        self._wall_pub = time.time()
        # and so does its first harvest interval, on this thread
        self._loop_ident = threading.get_ident()
        self._wait_ns = 0
        self._iv_ns[:] = [0] * len(self._iv_ns)
        self._iv = (self._t_pub, self._cpu_pub, run_delay_ns(),
                    self._wall_pub, self._gc.ns, BACKEND_COMPILES.value)

    def publish(self) -> None:
        """End of a turn: its wall is the time since the last
        publication, so a pass that popped nothing (and its source_wait)
        belongs to the turn that follows and the stages tile the turn."""
        now, cpu, wall = (time.perf_counter_ns(), time.thread_time_ns(),
                          time.time())
        ns = self._ns
        iv = self._iv_ns
        self._seq += 1
        children = self._children
        for i, v in enumerate(ns):
            if v:
                iv[i] += v
                if children[i] is None:
                    children[i] = _tm_turn_seconds.labels(
                        stage=_TURN_SLOTS[i])
                children[i].inc(v * 1e-9)
        _tm_turns.inc()
        if self._stats is not None:
            self._stats.note_turn(ns, now - self._t_pub, cpu - self._cpu_pub,
                                  self._wall_pub, self._seq)
        ns[:] = [0] * len(ns)
        self._t_pub, self._cpu_pub, self._wall_pub = now, cpu, wall

    def close_interval(self, interval_s: float, shed_events: int = 0,
                       worker_cpu_s: float = 0.0) -> dict | None:
        """A harvest closes the loop's interval since the one before it
        (the loop thread's harvests only: None on another thread). Its
        wall is split into LOOP_PARTS, each clamped into what the parts
        before it left, so they sum to the wall: the thread's CPU time,
        its run-queue delay (None where schedstat gives none: its share
        goes to `blocked`), the off-CPU part of its timed waits, and the
        rest. A gap of g harvest intervals misses floor(g) - 2 summaries,
        the benchmark's rule; their cause is `gc` where collections took
        half the gap's excess over two intervals or more, else `compile`
        where the backend compiled, else the largest part. Returns the
        record `harvest_gaps` keeps."""
        if threading.get_ident() != self._loop_ident:
            return None
        now, cpu, rq = (time.perf_counter_ns(), time.thread_time_ns(),
                        run_delay_ns())
        wall_clock = time.time()
        gc_ns, compiles = self._gc.ns, BACKEND_COMPILES.value
        t0, cpu0, rq0, start, gc0, compiles0 = self._iv
        wall = now - t0
        c = min(max(cpu - cpu0, 0), wall)
        r = (None if rq is None or rq0 is None
             else min(max(rq - rq0, 0), wall - c))
        w = min(self._wait_ns, wall - c - (r or 0))
        parts = {"cpu": c, "runqueue": r, "wait": w,
                 "blocked": wall - c - (r or 0) - w}
        gc_d, comp = gc_ns - gc0, int(compiles - compiles0)
        ns, iv = self._ns, self._iv_ns
        top = max(range(len(TURN_STAGES)), key=lambda i: iv[i] + ns[i])
        gap_s = wall * 1e-9
        missing = (max(int(gap_s // interval_s) - 2, 0)
                   if interval_s > 0 else 0)
        excess = gap_s - 2 * interval_s
        cause = ("gc" if excess > 0 and 2 * gc_d * 1e-9 >= excess
                 else "compile" if comp
                 else max(LOOP_PARTS, key=lambda p: parts[p] or 0))
        rec = {"start": start, "wall_s": gap_s,
               "parts": {p: None if v is None else v * 1e-9
                         for p, v in parts.items()},
               "gc_s": gc_d * 1e-9, "compiles": comp,
               "shed_events": int(shed_events),
               "worker_cpu_s": float(worker_cpu_s),
               "stage": TURN_STAGES[top], "cause": cause,
               "missing": missing}
        children = self._parts
        for p, v in parts.items():
            if v is not None:
                if children[p] is None:
                    children[p] = _tm_loop_seconds.labels(part=p)
                children[p].inc(v * 1e-9)
        _tm_loop_intervals.inc()
        if self._stats is not None:
            self._stats.note_interval(rec)
        # the next interval starts here; what this one counted of the
        # open turn comes off what that turn will publish
        self._iv = (now, cpu, rq, wall_clock, gc_ns, compiles)
        self._wait_ns = 0
        iv[:] = [-v for v in ns]
        return rec


_live_mu = threading.Lock()
_live: dict[str, PipelineStats] = {}


def live_stats() -> list[PipelineStats]:
    with _live_mu:
        return list(_live.values())
