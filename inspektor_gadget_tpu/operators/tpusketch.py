"""TPU sketch operator — the north-star analytics plane.

Any trace/top gadget can opt in (`--operator tpusketch` analogue): event
batches flow into a per-run SketchBundle on device (count-min + HLL +
entropy + top-k), with the autoencoder anomaly scorer optionally training
online on per-container distributions. Harvest ticks render heavy hitters /
distinct counts / entropy / anomaly scores as regular column rows, so the
existing formatter path displays them (BASELINE.json: "pkg/columns and
pkg/snapshotcombiner gain a sketch-column type").

Key choices per batch (instance params): which wire column feeds the
heavy-hitter stream (default key_hash), the distinct stream, and the
distribution stream — so `trace exec` counts comms, `trace dns` counts
qnames, `trace tcp` counts flows, with zero per-gadget code.
"""

from __future__ import annotations

import dataclasses
import math
import os
import threading
import time
from collections import deque
from pathlib import Path
from typing import Any, Callable

import numpy as np

import jax.numpy as jnp

import jax

from ..columns import col
from ..gadgets.context import GadgetContext
from ..gadgets.interface import GadgetDesc
from ..models.autoencoder import AEConfig, ae_init, anomaly_step
from ..ops import bundle_init, fold64_to_32
from ..ops.invertible import (InvSketch, class_weights, inv_capacity,
                              inv_decode, inv_init, inv_update,
                              parse_priority_classes,
                              validate_class_budget)
from ..ops.sketches import (bundle_digest_jit, bundle_ingest_jit,
                            bundle_stack_sharded, decode_digest,
                            make_bundle_harvest_sharded,
                            make_bundle_ingest_sharded, update_arm)
from ..ops.window import (LATE_AGE, window_capture, window_close,
                          window_open, window_planes_init,
                          window_planes_step)
from ..params import ParamDesc, ParamDescs, ParamError, Params, TypeHint
from ..params.validators import validate_int_range
from ..sources.batch import BATCH_COLUMNS, EventBatch, FoldedBatch
from ..sources.staging import H2DStager, PinnedBufferPool
from ..telemetry import counter, gauge, histogram
from ..telemetry.pipeline import (ANOMALY_SCORE, DISTS_STAGE, HARVEST_WAIT,
                                  LATE_CAUSES)
from ..telemetry.tracing import TRACER, annotation_class, device_annotation
from ..utils.grouping import SlotTable
from ..utils.logger import get_logger
from .operators import Operator, OperatorInstance, register

# the history package imports agent wire machinery — keep it lazy here
# (the param default/validator are the only module-load-time needs)
_DEFAULT_SCHEDULE = "1m@24h,10m@7d,1h@inf"


def _validate_history_schedule(value: str) -> None:
    from ..history import validate_schedule
    validate_schedule(value)


def _validate_chips(value: str) -> None:
    """`chips` is 'auto' (all local devices) or a positive int; the
    against-this-host checks (> local devices, 1-device host) run at
    instantiation, where the device count is known."""
    if value == "auto":
        return
    try:
        v = int(value)
    except ValueError:
        raise ValueError(f"{value!r} is not an integer or 'auto'") from None
    if v < 1:
        raise ValueError(f"chips must be >= 1, got {v}")


def _local_device_count() -> int:
    """Devices visible to the sharded ingest plane (module-level so tests
    can pin a topology without owning real chips)."""
    import jax
    return jax.local_device_count()


def _validate_priority_classes(value: str) -> None:
    """Grammar-level check at the params layer (budget needs inv-rows /
    inv-log2-buckets and runs at instantiation)."""
    parse_priority_classes(value)


def _validate_quantile_alpha(value: str) -> None:
    """DDSketch relative-error target: a float in (0, 0.3] — beyond that
    the bucket span collapses to a handful of buckets and every read is
    the same midpoint."""
    try:
        v = float(value)
    except ValueError:
        raise ValueError(f"{value!r} is not a float") from None
    if not (0.0 < v <= 0.3):
        raise ValueError(f"quantile-alpha must be in (0, 0.3], got {v}")

# device-plane telemetry (batch-grain; the histograms time dispatch-side —
# device completion is async and surfaces in the next blocking read)
_tm_events = counter("ig_tpusketch_events_total",
                     "events absorbed by the sketch plane", ("gadget",))
_tm_steps = counter("ig_tpusketch_steps_total",
                    "bundle_update device steps", ("gadget",))
_tm_step_rows = counter("ig_tpusketch_step_rows_total",
                        "rows the update steps ran at, pad rows included "
                        "(beside ig_tpusketch_events_total: the fill of "
                        "the steps)", ("gadget",))
_tm_arm_steps = counter("ig_tpusketch_update_arm_steps_total",
                        "bundle_update device steps by the arm the step "
                        "traced to (ops.sketches.update_arm)",
                        ("gadget", "arm"))
_tm_drops = counter("ig_tpusketch_drops_total",
                    "upstream drops folded into the bundle", ("gadget",))
# sharded ingest only: every family is labelled, so a one-chip run (which
# asks for no child) leaves none of these names in the registry
_tm_shard_rounds = counter(
    "ig_tpusketch_shard_rounds_total",
    "sharded update rounds dispatched: full (every lane held a batch) or "
    "flushed (a harvest, seal or checkpoint closed the round early)",
    ("gadget", "kind"))
_tm_shard_fillers = counter(
    "ig_tpusketch_shard_filler_lanes_total",
    "zero-weight filler lanes that flushed rounds rode", ("gadget",))
_tm_shard_lane_events = counter(
    "ig_tpusketch_shard_lane_events_total",
    "events of the batches parked on each ingest lane", ("gadget", "lane"))
_tm_harvests = counter("ig_tpusketch_harvests_total",
                       "harvest ticks", ("gadget",))
# late summaries by the benchmark's rule, with their cause, and harvest
# intervals in which the source shed (TurnClock.close_interval)
_tm_late = counter(
    "ig_tpusketch_summaries_late_total",
    "Summaries missing from harvest gaps of three intervals or more "
    "(floor(gap / interval) - 2 a gap), by what held the loop: gc, "
    "compile, cpu, runqueue, wait or blocked", ("gadget", "cause"))
_tm_shed_intervals = counter(
    "ig_tpusketch_shed_intervals_total",
    "Harvest intervals in which the source's drops rose", ("gadget",))
# history on only (labelled, so a history-off run leaves no such name)
_tm_slice_hh_entries = counter(
    "ig_history_slice_hh_entries_total",
    "(cell, key) entries of the slices' exact heavy-hitter table at each "
    "window seal: what the seal's slice work follows", ("gadget",))
_tm_slices = counter(
    "ig_history_slices_total",
    "subpopulation slices of the sealed windows by the decision their "
    "window's store made at their first appearance: admitted, or dropped "
    "over history-max-slices", ("gadget", "decision"))
# the windows themselves (labelled likewise): how many are open, how long
# after its end a window's capture came, and the events that came after
# their window had sealed (event-time windows only: history-lateness > 0)
_tm_open_windows = gauge(
    "ig_history_open_windows",
    "history windows open: one on the processing clock; on event time "
    "every window whose end the watermark has not passed", ("gadget",))
_tm_seal_delay_s = histogram(
    "ig_history_seal_delay_seconds",
    "a boundary seal's capture, wall time, less its window's end: a turn "
    "on the processing clock, history-lateness plus the backlog on event "
    "time", ("gadget",))
_tm_late_events = counter(
    "ig_history_late_events_total",
    "events older than the oldest open event-time window: absorbed into "
    "the cumulative sketches, in no window, carried in the next sealed "
    "window's `late`", ("gadget",))
# the two halves of a seal (labelled likewise): who finished the window, the
# boundaries that found the worker still on the one before, and what a
# finish takes wherever it runs
_tm_seals = counter(
    "ig_tpusketch_seals_total",
    "windows sealed, by who ran the finish: the seal worker (the served "
    "path's interval-driven seals) or the caller of seal_window()",
    ("gadget", "finish"))
_tm_seal_waits = counter(
    "ig_tpusketch_seal_waits_total",
    "window boundaries at which the loop thread waited for the seal worker "
    "to finish the window before", ("gadget",))
_tm_seal_finish_s = histogram(
    "ig_tpusketch_seal_finish_seconds",
    "a window's finish off the loop thread's state: read-backs, sort, "
    "slices, digest, append, hooks", ("gadget",))
# anomaly scorer on only (labelled likewise)
_tm_anomaly_steps = counter(
    "ig_tpusketch_anomaly_steps_total",
    "training-and-scoring steps of the anomaly scorer, one a harvest that "
    "has a container to score", ("gadget", "model"))
_tm_anomaly_slots = gauge(
    "ig_tpusketch_anomaly_slots",
    "rows of the per-container distribution array the scorer's step runs "
    "on (a power of two; it doubles when the containers outgrow it)",
    ("gadget",))
_tm_h2d = histogram("ig_tpusketch_h2d_seconds",
                    "host→device batch staging (pad/fold + transfer "
                    "dispatch)", ("gadget",))
_tm_update = histogram("ig_tpusketch_update_seconds",
                       "bundle_update step dispatch", ("gadget",))
_tm_harvest_s = histogram("ig_tpusketch_harvest_seconds",
                          "digest D2H + decode + scoring per harvest tick",
                          ("gadget",))
_tm_merge_s = histogram("ig_tpusketch_merge_seconds",
                        "bundle_merge latency (checkpoint resume)")
_tm_ckpt_ok = counter("ig_tpusketch_checkpoints_total",
                      "successful sketch-state checkpoints")
_tm_ckpt_fail = counter("ig_tpusketch_checkpoint_failures_total",
                        "failed sketch-state checkpoint attempts")
_tm_cand_overflow = counter(
    "ig_sketch_candidate_overflow_total",
    "runs whose top-k candidate population exceeded k (the harvest's "
    "heavy-hitter re-rank became approximate; summaries carry approx=True)",
    ("gadget",))
# latency quantile plane (ISSUE 16): events absorbed into the DDSketch
# row vs events whose value lane carried no magnitude (source without a
# value column, or a genuinely zero latency) — the denominator a reader
# needs to judge how much of a pX is the zero bucket
_tm_qt_events = counter(
    "ig_sketch_quantile_events_total",
    "events absorbed into the DDSketch quantile plane", ("gadget",))
_tm_qt_zero = counter(
    "ig_sketch_quantile_zero_total",
    "quantile-plane events whose value lane was zero (no magnitude — "
    "they land in the sketch's zero bucket, not a log bucket)")

_ckpt_log = get_logger("ig-tpu.tpusketch")

# The staged shape of a batch follows what the batch holds: the smallest
# power of two of rows that holds its events, from this floor (the gadget's
# documented default batch-size, so a default deployment has one program)
# up to the pad. A pad row is key 0 at weight 0 and changes no leaf, so
# fewer of them is the same state. A constant, not an option: every size of
# the ladder is primed before the source starts (`pre_gadget_run`).
STEP_ROWS_FLOOR = 8192

# How long before a summary is due the history window's deferrable work
# stands still (at most: a quarter of harvest-interval where that is
# shorter): the seal worker starts no further step of a finish, and the
# loop thread leaves the slices' backlog unfolded. Two threads of one
# interpreter share its lock turn and turn about, so a finish in flight
# doubles the loop's turn while it runs, and a summary whose turns it shares
# reads 9-20 ms where the others read 6; a backlog fold is 10-20 ms of one
# turn (exec-node.paced, PR 33). The worker asks between its steps, the
# longest of which (the slices' fold) is 20-40 ms under that sharing at 131k
# keys. A constant, not an option.
SEAL_QUIET_S = 0.06

# Fewest rows of the per-container distribution array, which the scorer's
# program runs at: a power of two, sized before the source starts to hold
# the containers the run is known to reach (`_expected_containers`) and
# doubled when more appear than it holds. A constant, not an option.
CONTAINER_SLOTS_FLOOR = 64

# window-plane device programs (history sealing, ops/window.py): one
# step a batch puts each event into the planes of its window (count-min
# ring slot, HLL, entropy, exact count); a seal's capture reads one
# window's planes and, where the next window opens, `window_open` zeroes
# the plane it reuses (on the processing clock both are one program,
# `window_close`). drops and the invertible/quantile planes come as
# deltas of the cumulative bundle (additive state is exactly subtractable)


# The ingest step is ops.sketches.bundle_ingest_jit: staged uint32 weights
# pass through as integer per-event weights (pad slots 0; pre-aggregated
# runs may weigh > 1), the fused-vs-scatter selection happens inside
# bundle_update_fused at trace time, and the second output is the fence
# token the stager blocks on (the donation/fence contract is documented
# on bundle_ingest_step).
_ingest_jit = bundle_ingest_jit


def _inv_class_ingest_step(s, keys, weights):
    """One priority class absorbing its share of a staged batch (weights
    zeroed outside the class's tenants). Second output is the fence
    token (fresh, never donated downstream) — the PR-7 contract."""
    out = inv_update(s, keys, weights)
    return out, out.count[0, :1] + 0


def _seal_snapshot(b):
    """What a seal reads of the (merged) bundle, in the order `_snapshot_host`
    unpacks: jitted, these are copies that the next ingest step, which
    donates the bundle, leaves alone."""
    inv = None if b.inv is None else (b.inv.count, b.inv.keysum, b.inv.fpsum)
    qt = (None if b.quantiles is None else
          (b.quantiles.counts, b.quantiles.zeros, b.quantiles.total))
    return b.drops, b.topk.keys, b.topk.overflow, inv, qt


_window_step_jit = jax.jit(window_planes_step, donate_argnums=0)
_window_capture_jit = jax.jit(window_capture)
_window_open_jit = jax.jit(window_open)
_window_close_jit = jax.jit(window_close)
_seal_snapshot_jit = jax.jit(_seal_snapshot)
_inv_class_jit = jax.jit(_inv_class_ingest_step, donate_argnums=0)


@dataclasses.dataclass
class _WindowCapture:
    """What `_capture_window` takes of a window at its batch boundary, for
    `_finish_window` to make a SealedWindow of on any thread: device arrays
    that nothing donates, the structures it swapped out of the instance,
    and copies of the little host state a seal reads."""

    window: int
    start_ts: float
    end_ts: float
    snap: tuple                 # _seal_snapshot of the (merged) bundle
    cms: Any                    # the window CMS's slot of this window ...
    counts: Any                 # ... and the candidates' estimates in it
    hll: Any                    # the window's HLL registers ...
    ent: Any                    # ... entropy histogram ...
    events: Any                 # ... and exact count of events
    late: int                   # late events since the seal before
    slices: Any                 # the window's WindowSlices
    shadow: tuple | None        # window shadow sample: keys, weights, capacity
    labels: tuple               # _labels_as_found: the label sample ring
    names: dict[int, str]       # the names cache


@dataclasses.dataclass
class HeavyHitterRow:
    """Rendered harvest row (sketch-column type)."""

    key: str = col("", width=24)
    count: int = col(0, width=12, dtype=np.int64)
    share: float = col(0.0, width=8, precision=4, dtype=np.float32)


@dataclasses.dataclass
class SketchSummary:
    events: int
    drops: int
    distinct: float
    entropy_bits: float
    heavy_hitters: list[tuple[int, int]]  # (key32, est count)
    anomaly: dict[int, float] | None = None  # mntns-slot → score
    epoch: int = 0
    names: dict[int, str] = dataclasses.field(default_factory=dict)  # key32 → label
    # candidate-ring accounting (ISSUE 15): True once the tracked top-k
    # population exceeded k — heavy_hitters is then the documented
    # approximation, not the exact re-rank
    approx: bool = False
    # invertible-plane decode of the (merged) sketch state: EXACT
    # (key32, count) pairs recovered with zero per-key storage, and the
    # subset of them the candidate ring MISSED (the observable win)
    decoded: list[tuple[int, int]] = dataclasses.field(default_factory=list)
    decoded_only: list[tuple[int, int]] = dataclasses.field(
        default_factory=list)
    inv: dict | None = None        # decode accounting {recovered, complete,
    #                                residual_events, capacity}
    classes: dict[str, dict] | None = None  # priority class → decode answer
    # latency quantile plane (ISSUE 16): DDSketch read of the merged
    # state — {p50, p90, p99, p999, zeros, total, underflow, alpha};
    # None when the plane is off (pre-plane consumers see no new field)
    quantiles: dict | None = None
    # pipeline health plane (ISSUE 18): PipelineStats.snapshot() at
    # harvest time — per-stage lag watermarks/quantiles, starved vs
    # saturated ticks, backpressure and occupancy. Excluded from
    # summary digests (capture/journal.py whitelist), encoded on the
    # wire only when present — pre-plane headers stay byte-identical
    pipeline: dict | None = None
    # accuracy audit plane (ISSUE 19): ops.accuracy.accuracy_block at
    # harvest time — per-stat analytic bounds + observed error vs the
    # shadow-sample ground truth. Only-when-present on the wire and
    # excluded from summary digests, same as `pipeline`; None when the
    # audit plane is off
    accuracy: dict | None = None
    # flat numeric access for detector rules lives in ONE place:
    # alerts.rules.summary_fields (handles this dataclass and the
    # wire-decoded dict shape alike)


# -- checkpoint/resume plumbing ---------------------------------------------
# The agent points this at --checkpoint-dir; every enabled instance then
# resumes from (bundle_merge) and periodically saves to
# <dir>/<category>-<gadget>[-scorer].npz — the role pinned BPF maps play for
# the reference's daemon restarts (pkg/gadgets/helpers.go:36).

_ckpt_dir: Path | None = None
_live: dict[str, "TpuSketchInstance"] = {}  # run_id → enabled instance
_live_mu = threading.Lock()


def set_checkpoint_dir(path: str | Path | None) -> None:
    global _ckpt_dir
    _ckpt_dir = Path(path) if path else None


def checkpoint_dir() -> Path | None:
    return _ckpt_dir


def live_instances() -> list["TpuSketchInstance"]:
    with _live_mu:
        return list(_live.values())


def _checkpoint_logged(inst: "TpuSketchInstance", retries: int = 1) -> bool:
    """One instance save with failure accounting: failures are logged and
    counted (checkpoint_failures_total), then retried once (a full disk or
    a transient file error often clears). Never raises; returns whether
    the save landed."""
    for attempt in range(1 + retries):
        try:
            inst.checkpoint()
            _tm_ckpt_ok.inc()
            return True
        except Exception as e:  # noqa: BLE001 — one bad save must not stop the rest
            _tm_ckpt_fail.inc()
            _ckpt_log.warning(
                "checkpoint of %s failed (attempt %d/%d): %r",
                getattr(inst, "_ckpt_key", "?"), attempt + 1, 1 + retries, e)
    return False


def checkpoint_all() -> int:
    """Save every live sketch instance; returns how many were saved."""
    saved = 0
    for inst in live_instances():
        if _checkpoint_logged(inst):
            saved += 1
    return saved


class TpuSketch(Operator):
    name = "tpusketch"

    def dependencies(self) -> list[str]:
        return []

    def can_operate_on(self, desc: GadgetDesc) -> bool:
        return True  # any batch-emitting gadget

    def instance_params(self) -> ParamDescs:
        return ParamDescs([
            ParamDesc(key="enable", default="false", type_hint=TypeHint.BOOL,
                      description="enable the TPU sketch plane"),
            ParamDesc(key="depth", default="4", type_hint=TypeHint.INT),
            ParamDesc(key="log2-width", default="16", type_hint=TypeHint.INT),
            ParamDesc(key="hll-p", default="14", type_hint=TypeHint.INT),
            ParamDesc(key="entropy-log2-width", default="12", type_hint=TypeHint.INT),
            ParamDesc(key="topk", default="128", type_hint=TypeHint.INT),
            ParamDesc(key="hh-column", default="key_hash",
                      description="wire column feeding the heavy-hitter stream"),
            ParamDesc(key="distinct-column", default="key_hash"),
            ParamDesc(key="dist-column", default="key_hash",
                      description="wire column feeding entropy/anomaly"),
            ParamDesc(key="anomaly", default="false", type_hint=TypeHint.BOOL,
                      description="train the autoencoder anomaly scorer"),
            ParamDesc(key="anomaly-model", default="ae",
                      possible_values=("ae", "vae", "seq"),
                      description="anomaly scorer family (distribution AE, "
                                  "distribution VAE, or sequence LM)"),
            ParamDesc(key="seq-window", default="256", type_hint=TypeHint.INT,
                      description="per-container token window for the "
                                  "sequence scorer"),
            ParamDesc(key="harvest-interval", default="1s",
                      type_hint=TypeHint.DURATION),
            ParamDesc(key="h2d-depth", default="2", type_hint=TypeHint.INT,
                      description="H2D double-buffer depth: transfers of "
                                  "batch k+1..k+N-1 overlap device compute "
                                  "of batch k"),
            # invertible heavy-key plane (ISSUE 15): recover WHICH keys
            # from merged sketch state alone — rides the fused kernel as
            # extra grid planes, merges via the existing psum algebra
            ParamDesc(key="invertible", default="false",
                      type_hint=TypeHint.BOOL,
                      description="add the invertible heavy-key plane: "
                                  "decode of (merged) state recovers "
                                  "exact (key, count) pairs with zero "
                                  "per-key storage"),
            ParamDesc(key="inv-log2-buckets", default="12",
                      type_hint=TypeHint.INT,
                      validator=validate_int_range(lo=6, hi=20),
                      description="buckets per invertible row (decode "
                                  "capacity ~ rows*buckets/4 distinct "
                                  "keys)"),
            ParamDesc(key="inv-rows", default="3", type_hint=TypeHint.INT,
                      validator=validate_int_range(lo=2, hi=8),
                      description="invertible hash rows (peeling "
                                  "redundancy; 3 is the sweet spot)"),
            ParamDesc(key="priority-classes", default="",
                      validator=_validate_priority_classes,
                      description="PSketch-style accuracy classes: "
                                  "name=log2buckets:mntns|mntns,... with "
                                  "one '*' catch-all (e.g. "
                                  "hot=12:101|102,rest=10:*); classes "
                                  "partition the base invertible memory "
                                  "budget so hot tenants keep decode "
                                  "fidelity when the whole stream "
                                  "overflows it"),
            # latency quantile plane (ISSUE 16): a DDSketch row rides the
            # fused kernel as one more grid plane; harvest answers
            # p50/p90/p99/p99.9 with <= alpha relative error, merges by
            # bucket-wise add (windows, pushdown, collective harvest)
            ParamDesc(key="quantiles", default="false",
                      type_hint=TypeHint.BOOL,
                      description="add the DDSketch latency quantile "
                                  "plane: per-event magnitudes (latency "
                                  "ns / bytes) bucket into one more fused "
                                  "grid plane; harvests carry "
                                  "p50/p90/p99/p99.9"),
            ParamDesc(key="quantile-alpha", default="0.01",
                      validator=_validate_quantile_alpha,
                      description="DDSketch relative-error target: every "
                                  "quantile read is within alpha of the "
                                  "true value (0.01 = 1%)"),
            ParamDesc(key="quantile-field", default="aux1",
                      description="wire column feeding the value lane on "
                                  "the EventBatch path (aux1 carries "
                                  "latency ns / byte counts for the "
                                  "value-bearing kinds; folded batches "
                                  "carry their own lane)"),
            # accuracy audit plane (ISSUE 19): a host-side deterministic
            # bottom-k shadow sample rides ingest; harvests then carry
            # OBSERVED error next to the analytic bound (which is free
            # and always present, plane on or off)
            ParamDesc(key="audit-sample", default="0",
                      type_hint=TypeHint.INT,
                      validator=validate_int_range(lo=0),
                      description="shadow-sample capacity for the "
                                  "accuracy audit plane (keys held as "
                                  "ground truth; 0 = plane off — "
                                  "summaries then carry analytic bounds "
                                  "only)"),
            # multi-chip sharded ingest (ISSUE 14): one fused bundle
            # replica per chip, batches round-robined onto per-device
            # lanes, psum/pmax collective merge at harvest only
            ParamDesc(key="shard-ingest", default="false",
                      type_hint=TypeHint.BOOL,
                      description="shard the staged ingest plane across "
                                  "local devices (round-robin lanes, "
                                  "collective harvest; needs >= 2 local "
                                  "devices; IG_SHARD_DISABLE=1 forces the "
                                  "single-chip path)"),
            ParamDesc(key="chips", default="auto",
                      validator=_validate_chips,
                      description="device lanes for shard-ingest: 'auto' "
                                  "= all local devices; 1 = the exact "
                                  "single-chip path; must not exceed the "
                                  "local device count"),
            # sketch-history plane: seal one mergeable window per
            # boundary into the node's sealed-window store (history/)
            ParamDesc(key="history", default="false", type_hint=TypeHint.BOOL,
                      description="seal time-windowed sketch snapshots "
                                  "into the node's history store"),
            ParamDesc(key="history-interval", default="10s",
                      type_hint=TypeHint.DURATION,
                      description="window length; 0 seals one window per "
                                  "harvest (the deterministic-replay mode)"),
            ParamDesc(key="history-lateness", default="0s",
                      type_hint=TypeHint.DURATION,
                      description="0: windows cut on the processing clock; "
                                  "> 0: windows of history-interval aligned "
                                  "to event time (each event's ts), sealed "
                                  "once the watermark (the newest event "
                                  "time less this lateness) passes their "
                                  "end; older events are counted late"),
            ParamDesc(key="history-dir", default="",
                      description="override the node history area for this "
                                  "run ($IG_HISTORY_DIR / agent "
                                  "--history-dir otherwise)"),
            ParamDesc(key="history-log2-width", default="12",
                      type_hint=TypeHint.INT,
                      description="per-window CMS width (the WindowedCMS "
                                  "ring's table)"),
            ParamDesc(key="history-slots", default="8",
                      type_hint=TypeHint.INT,
                      description="WindowedCMS ring slots (live last-k "
                                  "window view)"),
            ParamDesc(key="history-max-slices", default="256",
                      type_hint=TypeHint.INT,
                      description="subpopulation slices tracked per window "
                                  "(admitted at first appearance; overflow "
                                  "dropped and accounted)"),
            # tiered history lifecycle (history/lifecycle.py +
            # history/archive.py): retention as a POLICY — aged windows
            # compact into coarser super-windows per the resolution
            # schedule, fully-compacted cold segments offload to the
            # archive tier. All four validated LOUDLY before the run.
            ParamDesc(key="history-compact", default="false",
                      type_hint=TypeHint.BOOL,
                      description="run time-decayed compaction over this "
                                  "run's history store (aged windows merge "
                                  "into coarser super-windows per "
                                  "history-schedule)"),
            ParamDesc(key="history-schedule", default=_DEFAULT_SCHEDULE,
                      validator=_validate_history_schedule,
                      description="resolution schedule "
                                  "res@horizon[,res@horizon...] (e.g. "
                                  "1m@24h,10m@7d,1h@inf); the last horizon "
                                  "must be inf"),
            ParamDesc(key="history-archive-dir", default="",
                      description="offload fully-compacted cold segments "
                                  "to this archive root (object-store-"
                                  "shaped backend; filesystem impl today) "
                                  "with manifest-driven rehydration"),
            ParamDesc(key="history-archive-cache-bytes",
                      default=str(64 << 20), type_hint=TypeHint.INT,
                      validator=validate_int_range(lo=1 << 16),
                      description="rehydration cache budget (LRU by "
                                  "bytes, hit/miss counted)"),
            # standing-query plane (queries/): continuous questions
            # answered incrementally at each seal tick instead of
            # re-folded per request; needs the history plane (the fold
            # input IS the sealed-window stream)
            ParamDesc(key="standing-queries", default="",
                      description="standing-query document (JSON/YAML "
                                  "list of {id, stats, range, key?, "
                                  "top?, every?}) or @/path/to/file; "
                                  "answers materialize at every seal "
                                  "tick and publish on the summary tier"),
            ParamDesc(key="query-cache-bytes", default=str(8 << 20),
                      type_hint=TypeHint.INT,
                      validator=validate_int_range(lo=1 << 10),
                      description="digest-keyed result cache budget "
                                  "(LRU by bytes; hits serve reads with "
                                  "zero window folds)"),
            ParamDesc(key="query-refresh", default="1",
                      type_hint=TypeHint.INT,
                      validator=validate_int_range(lo=1),
                      description="default publish cadence in seal "
                                  "ticks for queries without an "
                                  "explicit 'every'"),
            ParamDesc(key="query-max-range", default="24h",
                      description="cap on any standing query's sliding "
                                  "range (bounds per-query window "
                                  "retention; duration, e.g. 24h)"),
        ])

    def instantiate(self, ctx: GadgetContext, gadget: Any,
                    instance_params: Params) -> "TpuSketchInstance":
        return TpuSketchInstance(self, ctx, gadget, instance_params)


class TpuSketchInstance(OperatorInstance):
    def __init__(self, op: TpuSketch, ctx: GadgetContext, gadget: Any,
                 params: Params):
        super().__init__(op.name)
        self.ctx = ctx
        self.gadget = gadget
        p = params
        self.enabled = p.get("enable").as_bool() if "enable" in p else False
        if not self.enabled:
            return
        self.hh_col = p.get("hh-column").as_string()
        self.distinct_col = p.get("distinct-column").as_string()
        self.dist_col = p.get("dist-column").as_string()
        self.harvest_interval = p.get("harvest-interval").as_duration() or 1.0
        # device-plane spans parent to the run span; the checkpointer and
        # post_gadget_run threads have no ambient span, so keep it pinned
        self._trace_parent = ctx.extra.get("trace_ctx")
        # serializes bundle read/update: bundle_update_jit DONATES its
        # input, so the checkpointer thread reading self.bundle while the
        # run thread dispatches an update would read deleted buffers
        self._bundle_mu = threading.Lock()
        g = ctx.desc.full_name
        self._m_events = _tm_events.labels(gadget=g)
        self._m_steps = _tm_steps.labels(gadget=g)
        self._m_drops = _tm_drops.labels(gadget=g)
        self._m_harvests = _tm_harvests.labels(gadget=g)
        self._m_h2d = _tm_h2d.labels(gadget=g)
        self._m_update = _tm_update.labels(gadget=g)
        self._m_harvest_s = _tm_harvest_s.labels(gadget=g)
        self._m_late = {c: _tm_late.labels(gadget=g, cause=c)
                        for c in LATE_CAUSES}
        self._m_shed_intervals = _tm_shed_intervals.labels(gadget=g)
        self._m_qt_events = _tm_qt_events.labels(gadget=g)
        # the stages of the batch turn this instance times itself
        # (telemetry/pipeline.py TURN_STAGES): sibling `ig:` annotations
        # on the profiler's clock, seconds into the run's TurnClock
        self.times_own_stages = True
        self._turn = turn = ctx.turn
        (self._st_fold, self._st_route, self._st_h2d, self._st_restage,
         self._st_update, self._st_planes, self._st_slices, self._st_inv,
         self._st_post, self._st_seal, self._st_merge, self._st_harvest) = (
             turn.stage("tpusketch_" + n) for n in (
                 "fold", "window_route", "h2d", "shard_restage", "update",
                 "window_planes", "slices", "inv_classes", "post", "seal",
                 "shard_merge", "harvest"))
        # the timed waits of the loop thread: the digest's read, the
        # stager's retire, the seal drain's join
        self._w_harvest = turn.wait(HARVEST_WAIT)
        self._w_retire = turn.wait()
        self._w_join = turn.wait()
        # the source's drops at the last harvest, and the seal worker's
        # CPU time a finish, appended by the worker as it ends and taken
        # by the next harvest (harvest_gaps' shed_events, worker_cpu_s)
        self._iv_drops = 0
        self._worker_cpu: deque[int] = deque()
        # -- invertible heavy-key plane + priority classes (ISSUE 15) ----
        # All validation answers a typed ParamError HERE, before the
        # first batch: classes without the plane, and class geometries
        # overrunning the base memory budget, are config errors.
        self._inv_on = (p.get("invertible").as_bool()
                        if "invertible" in p else False)
        self._inv_rows = (p.get("inv-rows").as_int()
                          if "inv-rows" in p else 3)
        self._inv_lb = (p.get("inv-log2-buckets").as_int()
                        if "inv-log2-buckets" in p else 12)
        classes_spec = (p.get("priority-classes").as_string()
                        if "priority-classes" in p else "")
        self._inv_classes: list[tuple[Any, InvSketch]] = []
        if classes_spec:
            if not self._inv_on:
                raise ParamError(
                    "param 'priority-classes': needs 'invertible true' — "
                    "accuracy classes partition the invertible plane's "
                    "memory budget")
            try:
                cls = parse_priority_classes(classes_spec)
                validate_class_budget(cls, rows=self._inv_rows,
                                      log2_buckets=self._inv_lb)
            except ValueError as e:
                raise ParamError(f"param 'priority-classes': {e}") from None
            self._inv_classes = [
                (c, inv_init(self._inv_rows, c.log2_buckets)) for c in cls]
        self._overflow_counted = False
        # -- latency quantile plane (ISSUE 16) ----------------------------
        # Same loud-validation discipline: every quantile misconfig is a
        # typed ParamError before the first batch. quantile-alpha's range
        # is the param validator's job; the cross-param rules live here.
        self._qt_on = (p.get("quantiles").as_bool()
                       if "quantiles" in p else False)
        self._qt_alpha = (float(p.get("quantile-alpha").as_string())
                          if "quantile-alpha" in p else 0.01)
        self._qt_field = (p.get("quantile-field").as_string()
                          if "quantile-field" in p else "aux1")
        self._qt_minv = 1.0   # value lane is integer ns/bytes: 0 is the
        #                       zero bucket, 1 the smallest magnitude
        if not self._qt_on:
            if self._qt_alpha != 0.01:
                raise ParamError(
                    "param 'quantile-alpha': needs 'quantiles true' — "
                    "the error target configures the DDSketch plane")
            if self._qt_field != "aux1":
                raise ParamError(
                    "param 'quantile-field': needs 'quantiles true' — "
                    "the value lane only exists with the quantile plane")
        elif self._qt_field not in BATCH_COLUMNS:
            raise ParamError(
                f"param 'quantile-field': {self._qt_field!r} is not a "
                f"wire column (one of {', '.join(BATCH_COLUMNS)})")
        # -- event-time history windows -----------------------------------
        # The same loud validation, before the first batch. The invertible
        # and quantile planes ride a window as deltas of the cumulative
        # bundle between two boundaries, which no event time gives
        lateness = (p.get("history-lateness").as_duration()
                    if "history-lateness" in p else 0.0) or 0.0
        self._event_time = lateness > 0
        if lateness < 0:
            raise ParamError("param 'history-lateness': must not be negative")
        if self._event_time:
            if not (p.get("history").as_bool() if "history" in p else False):
                raise ParamError("param 'history-lateness': needs 'history "
                                 "true' — it decides when windows seal")
            if self._inv_on or self._qt_on:
                raise ParamError(
                    "param 'history-lateness': not with 'invertible' or "
                    "'quantiles' — their window planes are deltas of the "
                    "cumulative state between two processing-time "
                    "boundaries, not per event-time window")
            interval = (p.get("history-interval").as_duration()
                        if "history-interval" in p else 10.0) or 0.0
            if interval <= 0:
                raise ParamError(
                    "param 'history-lateness': needs a history-interval > 0 "
                    "(0 seals a window a harvest, on the processing clock)")
            slots = (p.get("history-slots").as_int()
                     if "history-slots" in p else 8)
            if slots < 2 + math.ceil(lateness / interval):
                raise ParamError(
                    f"param 'history-slots': {slots} ring slots cannot hold "
                    f"the {2 + math.ceil(lateness / interval)} windows "
                    f"history-lateness keeps open")
        # -- accuracy audit plane (ISSUE 19) ------------------------------
        # Host-side deterministic bottom-k shadow sample: run-scoped for
        # harvest audits, window-scoped for sealed-window rs lanes. Off
        # (capacity 0) costs nothing — no sample, no gauges registered,
        # byte-identical summaries/digests.
        self._audit_k = (p.get("audit-sample").as_int()
                         if "audit-sample" in p else 0)
        self._shadow = None
        self._win_shadow = None
        self._astats = None
        if self._audit_k > 0:
            from ..ops.accuracy import AccuracyStats, ShadowSample
            self._shadow = ShadowSample(self._audit_k)
            self._win_shadow = ShadowSample(self._audit_k)
            self._astats = AccuracyStats(ctx.run_id, ctx.desc.full_name)
        self.bundle = bundle_init(
            depth=p.get("depth").as_int(),
            log2_width=p.get("log2-width").as_int(),
            hll_p=p.get("hll-p").as_int(),
            entropy_log2_width=p.get("entropy-log2-width").as_int(),
            k=p.get("topk").as_int(),
            inv_rows=self._inv_rows if self._inv_on else 0,
            inv_log2_buckets=self._inv_lb,
            quantiles=self._qt_on,
            quantile_alpha=self._qt_alpha,
            quantile_min_value=self._qt_minv,
        )
        self.anomaly_on = p.get("anomaly").as_bool()
        self.anomaly_model = (p.get("anomaly-model").as_string()
                              if "anomaly-model" in p else "ae")
        self.scorer = None
        # per-container distributions (`ae` / `vae`): one [slots, dim]
        # float32 array behind a mntns -> slot table; the slots are a power
        # of two, sized before the source starts to hold the containers the
        # run is known to reach (`pre_gadget_run`) and doubled when more
        # appear, which is the only time the scorer's program changes shape
        self._containers = SlotTable()
        self._container_counts: np.ndarray | None = None
        self._anomaly_step = None
        self._container_seqs: dict[int, list[int]] = {}
        self._seq_window = (p.get("seq-window").as_int()
                            if "seq-window" in p else 256)
        if self.anomaly_on:
            dim = 1 << p.get("entropy-log2-width").as_int()
            if self.anomaly_model == "vae":
                from ..models import vae
                self._ae_cfg = vae.VAEConfig(input_dim=dim, hidden_dim=256,
                                             latent_dim=64)
                self.scorer = vae.vae_init(self._ae_cfg)
                self._anomaly_step = vae.anomaly_step
            elif self.anomaly_model == "seq":
                from ..models.seqmodel import SeqConfig, seq_init
                self._ae_cfg = SeqConfig(vocab=min(dim, 512))
                self.scorer = seq_init(self._ae_cfg)
            else:
                self._ae_cfg = AEConfig(input_dim=dim, hidden_dim=256,
                                        latent_dim=64)
                self.scorer = ae_init(self._ae_cfg)
                self._anomaly_step = anomaly_step
            if self._anomaly_step is not None:
                self._container_counts = np.zeros(
                    (CONTAINER_SLOTS_FLOOR, dim), dtype=np.float32)
                self._m_anomaly_steps = _tm_anomaly_steps.labels(
                    gadget=g, model=self.anomaly_model)
                self._m_anomaly_slots = _tm_anomaly_slots.labels(gadget=g)
                self._m_anomaly_slots.set(CONTAINER_SLOTS_FLOOR)
                self._anomaly_steps = 0
                self._primed_slots = 0      # set by pre_gadget_run
        self._drops_seen = 0
        self._last_harvest = time.monotonic()
        self._epoch = 0
        self._names: dict[int, str] = {}
        self.on_summary: Callable[[SketchSummary], None] | None = ctx.extra.get(
            "on_sketch_summary")
        # device batch shapes (pad/mask): the pad is the gadget's own batch
        # size as a power of two, and the capacity of the pinned blocks; a
        # batch is staged and stepped at the power of two that holds it
        # (`_step_rows`: STEP_ROWS_FLOOR .. pad, all of them primed by
        # `pre_gadget_run`, each a fresh ~30 s TPU compile when cold). A
        # batch over the pad ratchets the pad up and compiles where it lands
        pad = STEP_ROWS_FLOOR
        if "batch-size" in ctx.gadget_params:
            bs = ctx.gadget_params.get("batch-size").as_int()
            if bs > 0:
                pad = max(pad, 1 << (bs - 1).bit_length())
        self._pad = pad
        self._m_step_rows = _tm_step_rows.labels(gadget=g)
        # per size of step: the arm it traces to with its counter, and
        # the steps dispatched at it
        self._arms: dict[int, tuple[str, Any]] = {}
        self._steps_by_rows: dict[int, int] = {}
        self._arm = self._note_arm(pad)[0]
        # pinned staging pool + depth-N H2D double buffer (created lazily
        # at the first batch, once the pad shape is known for real)
        self._h2d_depth = (p.get("h2d-depth").as_int()
                           if "h2d-depth" in p else 2)
        self._pool: PinnedBufferPool | None = None
        self._stager: H2DStager | None = None
        # -- multi-chip sharded ingest (ISSUE 14 tentpole) ----------------
        # All topology checks answer a typed ParamError HERE, before the
        # first batch (the FetchWindows loud-validation discipline):
        # chips beyond the host, sharding a 1-device host, and a
        # batch-size that can't fill whole rounds are config errors, not
        # runtime surprises. chips=1 (or IG_SHARD_DISABLE=1) pins the
        # EXACT single-chip PR-7 path — the shard machinery is never
        # built, so there is zero regression risk behind the default.
        self._shard_on = False
        self._chips = 1
        shard_req = (p.get("shard-ingest").as_bool()
                     if "shard-ingest" in p else False)
        chips_s = p.get("chips").as_string() if "chips" in p else "auto"
        ndev = _local_device_count()
        if os.environ.get("IG_SHARD_DISABLE", "") == "1":
            # the escape hatch outranks every shard topology check: a
            # fleet-wide config (chips=4) must still start on a host
            # that degraded to fewer devices when the operator forces
            # the single-chip path
            if shard_req or chips_s != "auto":
                _ckpt_log.warning(
                    "IG_SHARD_DISABLE=1: shard-ingest/chips params are "
                    "inert — forced to the single-chip path")
            shard_req = False
        elif chips_s != "auto" and int(chips_s) > ndev:
            raise ParamError(
                f"param 'chips': {chips_s} exceeds the {ndev} local "
                f"device(s) on this host")
        if shard_req:
            if ndev < 2:
                raise ParamError(
                    "param 'shard-ingest': this host exposes 1 device — "
                    "sharded ingest needs >= 2 local devices (chips=1 is "
                    "the single-chip path and needs no flag)")
            self._chips = ndev if chips_s == "auto" else int(chips_s)
            if self._chips >= 2 and "batch-size" in ctx.gadget_params:
                bs = ctx.gadget_params.get("batch-size").as_int()
                if bs > 0 and bs % self._chips:
                    raise ParamError(
                        f"param 'chips': batch-size {bs} is not divisible "
                        f"by chips {self._chips} — round-robin lane fills "
                        f"need whole batches per lane")
            self._shard_on = self._chips >= 2
        # sharded state is built lazily at the first batch (mesh, jits,
        # per-device pools). Round-robin assignment is the monotonic
        # _next_lane counter — batch i ALWAYS lands on lane i mod chips,
        # independent of when a harvest/checkpoint thread flushes the
        # open round — and _pending maps lane → its staged-but-
        # undispatched batch (staged arrays + stager slot + drops +
        # window-plane fence tokens); a full round dispatches ONE
        # shard_map step
        self._mesh = None
        self._sharded = None
        self._ingest_sharded = None
        self._harvest_sharded = None
        self._lane_pools: list[PinnedBufferPool] = []
        self._lane_stagers: list[H2DStager] = []
        self._lane_zeros: list = []
        self._next_lane = 0
        self._pending: dict[int, dict] = {}
        # late-enrichment sample ring (display-only work moved OFF the
        # ingest path): per batch two vectorized slice writes capture a
        # few (k64, k32, comm) rows; names resolve lazily at harvest/seal
        self._lbl_cap = 1024
        self._lbl_k64 = np.zeros(self._lbl_cap, np.uint64)
        self._lbl_k32 = np.zeros(self._lbl_cap, np.uint32)
        self._lbl_comm = np.zeros((self._lbl_cap, 8), np.uint8)
        self._lbl_i = 0
        # self-observability feed for top/sketch (top/ebpf analogue)
        from ..gadgets.top.sketch import SketchStatsSource
        self._stats = SketchStatsSource(ctx.run_id, ctx.desc.full_name)
        self._stats.register()
        # pipeline health plane (ISSUE 18): per-stage lag watermarks,
        # starved/saturated stager ticks, backpressure — fed by the
        # stagers and the ingest loop, read by harvest/DumpState/doctor
        from ..telemetry.pipeline import PipelineStats
        self._pstats = PipelineStats(ctx.run_id, ctx.desc.full_name)
        self._pstats.register()
        turn.attach(self._pstats)
        if self.anomaly_on:
            # only a run with the scorer carries these names
            self._st_dists = turn.stage(DISTS_STAGE)
            turn.open_stages(DISTS_STAGE, ANOMALY_SCORE)
        if self._astats is not None:
            # registered only when the audit plane is on: a plane-off
            # run must leave no accuracy gauges or live rows behind
            self._astats.register()
        # -- sketch-history plane (sealed windows, history/) --------------
        self._hist_on = p.get("history").as_bool() if "history" in p else False
        # the window age of a batch on the processing clock: the open one
        self._age0 = jnp.zeros((), jnp.int32) if self._hist_on else None
        if self._hist_on:
            self._hist_interval = (p.get("history-interval").as_duration()
                                   if "history-interval" in p else 10.0) or 0.0
            self._hist_dir = (p.get("history-dir").as_string()
                              if "history-dir" in p else "") or None
            self._hist_log2w = (p.get("history-log2-width").as_int()
                                if "history-log2-width" in p else 12)
            self._hist_slots = (p.get("history-slots").as_int()
                                if "history-slots" in p else 8)
            self._hist_max_slices = (p.get("history-max-slices").as_int()
                                     if "history-max-slices" in p else 256)
            # replay reseals under the RECORDED identity and clock so the
            # window digests reproduce byte-identically (the determinism
            # contract the e2e asserts); live runs use wall time
            self._hist_gadget = (ctx.extra.get("history_gadget")
                                 or ctx.desc.full_name)
            self._hist_clock = (ctx.extra.get("history_clock")
                                or ctx.extra.get("alerts_clock") or time.time)
            # planes for every window that can be open at once: one on the
            # processing clock; on event time the window the watermark is
            # in, those the lateness spans, and the newest
            n_open = (2 + math.ceil(lateness / self._hist_interval)
                      if self._event_time else 1)
            self._wplanes = window_planes_init(
                n_open, self._hist_slots, p.get("depth").as_int(),
                self._hist_log2w, p.get("hll-p").as_int(),
                p.get("entropy-log2-width").as_int())
            self._win_n = 0
            self._win_start = self._hist_clock()
            # event time, in ns of the events' own clock: windows
            # [k W, (k+1) W); `_ev_lo` the oldest the watermark has not
            # passed, `_ev_hi` the newest opened (below _ev_lo: none open)
            self._win_ns = round(self._hist_interval * 1e9)
            self._late_ns = round(lateness * 1e9)
            self._ev_lo: int | None = None
            self._ev_hi = -1
            self._ev_newest_ns = self._ev_batch_newest = 0
            # the clock at the last batch: how long the source has been idle
            self._ev_batch_at = self._win_start
            self._late_pending = 0
            self._late_total = 0
            # events routed to each open window, and its subpopulation
            # store (processing time: window 0, the open one)
            self._win_events: dict[int, int] = {}
            self._win_slices: dict[int, Any] = {}
            self._m_open_windows = _tm_open_windows.labels(gadget=g)
            self._m_open_windows.set(0 if self._event_time else 1)
            self._m_seal_delay = _tm_seal_delay_s.labels(gadget=g)
            if self._event_time:
                self._m_late_events = _tm_late_events.labels(gadget=g)
            from ..history import HISTORY, WindowSlices
            self._slices_cls = WindowSlices
            self._last_slices: dict[str, int] | None = None
            self._m_slice_hh = _tm_slice_hh_entries.labels(gadget=g)
            self._m_slices = {
                d: _tm_slices.labels(gadget=g, decision=d)
                for d in ("admitted", "dropped")}
            # the two halves of a seal: `_capture_window` on the thread
            # that owns the live state, `_finish_window` on the caller of
            # seal_window() or, for the served path's interval-driven
            # seals, on one worker thread a window, joined at the next
            # boundary: it holds one window at most, and windows reach the
            # store in order
            self._seal_thread: threading.Thread | None = None
            # set: the worker may start its next step (`_seal_step`); the
            # loop thread clears it while a summary is nearly due
            # (`_summary_near`, asked once a turn)
            self._seal_clear = threading.Event()
            self._seal_clear.set()
            self._seal_quiet = min(SEAL_QUIET_S, self.harvest_interval / 4)
            self._summary_near = False
            self._seal_stats = {"worker": 0, "caller": 0, "waited": 0,
                                "finish_ms_last": 0.0, "finish_ms_max": 0.0}
            self._m_seals = {f: _tm_seals.labels(gadget=g, finish=f)
                             for f in ("worker", "caller")}
            self._m_seal_waits = _tm_seal_waits.labels(gadget=g)
            self._m_seal_finish_s = _tm_seal_finish_s.labels(gadget=g)
            try:
                self._hist_writer = HISTORY.writer_for(
                    self._hist_gadget, node=ctx.extra.get("node", "") or "",
                    run_id=ctx.run_id,
                    params=ctx.operator_params.copy_to_map(),
                    base_dir=self._hist_dir)
            except (OSError, ValueError) as e:
                _ckpt_log.warning("history store open failed (sealing "
                                  "disabled for this run): %r", e)
                self._hist_on = False
        # tiered lifecycle: compaction engine + archive tier opt-ins
        self._hist_engine = None
        if self._hist_on:
            arch_dir = (p.get("history-archive-dir").as_string()
                        if "history-archive-dir" in p else "")
            if arch_dir:
                from ..history import HISTORY
                cache_b = (p.get("history-archive-cache-bytes").as_int()
                           if "history-archive-cache-bytes" in p
                           else 64 << 20)
                HISTORY.set_archive(arch_dir, cache_b)
            compact = (p.get("history-compact").as_bool()
                       if "history-compact" in p else False)
            if compact:
                from ..history import CompactionEngine
                schedule = (p.get("history-schedule").as_string()
                            if "history-schedule" in p
                            else _DEFAULT_SCHEDULE)
                # ages measure against the same (injectable) clock the
                # sealer stamps windows with — a replay/sim clock must
                # not see its windows as months old
                self._hist_engine = CompactionEngine(
                    schedule, clock=self._hist_clock)
        # -- standing-query plane (queries/) ------------------------------
        # Same loud-validation discipline as the invertible/quantile
        # matrices: every misconfig is a typed ParamError before the
        # first batch, never a surprise mid-run.
        self._sq_engine = None
        sq_doc = (p.get("standing-queries").as_string()
                  if "standing-queries" in p else "")
        sq_cache_b = (p.get("query-cache-bytes").as_int()
                      if "query-cache-bytes" in p else 8 << 20)
        sq_refresh = (p.get("query-refresh").as_int()
                      if "query-refresh" in p else 1)
        sq_max_range = (p.get("query-max-range").as_duration()
                        if "query-max-range" in p else 86400.0)
        if not sq_doc:
            if sq_cache_b != 8 << 20:
                raise ParamError(
                    "param 'query-cache-bytes': needs 'standing-queries' "
                    "— the result cache fronts materialized answers")
            if sq_refresh != 1:
                raise ParamError(
                    "param 'query-refresh': needs 'standing-queries' — "
                    "the cadence applies to registered queries")
            if sq_max_range != 86400.0:
                raise ParamError(
                    "param 'query-max-range': needs 'standing-queries' "
                    "— the cap bounds registered queries' ranges")
        else:
            if not (p.get("history").as_bool() if "history" in p
                    else False):
                raise ParamError(
                    "param 'standing-queries': needs 'history true' — "
                    "materialized answers fold the sealed-window stream")
            from ..queries import (QueryError, StandingQueryEngine,
                                   load_queries, load_queries_file)
            try:
                if sq_doc.startswith("@"):
                    specs = load_queries_file(
                        sq_doc[1:], default_every=sq_refresh,
                        max_range_s=sq_max_range)
                else:
                    specs = load_queries(
                        sq_doc, default_every=sq_refresh,
                        max_range_s=sq_max_range)
            except QueryError as e:
                raise ParamError(
                    f"param 'standing-queries': {e}") from None
            self._sq_engine = StandingQueryEngine(
                specs, gadget=self._hist_gadget,
                node=ctx.extra.get("node", "") or "",
                cache_bytes=sq_cache_b)
            from ..queries import engine as _queries_engine
            _queries_engine.register(ctx.run_id, self._sq_engine)
        # checkpoint/resume: keyed by gadget identity so a restarted run
        # (new run_id) finds its predecessor's state
        self._ckpt_key = ctx.desc.full_name.replace("/", "-")
        self._resume()
        if self._hist_on:
            # the first window's baseline, AFTER resume: window deltas must
            # exclude the prior state bundle_merge just absorbed. Every
            # later one is the snapshot of the window before, which its
            # finish leaves here (finishes run one at a time, in order;
            # the loop thread never reads it). The empty-window test is
            # the host's own count of events, so it reads nothing back
            self._win_base = self._snapshot_host(_seal_snapshot(self.bundle))
            self._win_host_events = self._stats.events
        with _live_mu:
            _live[ctx.run_id] = self

    def _span(self, name: str, **attrs):
        """Device-plane span: nests under the enrich span when called from
        the operator chain (ambient current), else under the run span."""
        cur = TRACER.current_context()
        return TRACER.span(name, parent=cur if cur is not None
                           else self._trace_parent, attrs=attrs)

    def _note_watermarks(self, pop_ts: float, oldest_ts: float,
                         lane: int = 0) -> None:
        """Batch-grain lag watermarks (pipeline health plane): host lag
        = pop − oldest event, device lag = dispatch (now) − pop — two
        clock reads per BATCH, nothing per event. Unstamped batches
        (0.0 fields: non-bridge producers) degrade to zero lag rather
        than an epoch-sized one."""
        now = time.time()
        if pop_ts <= 0.0:
            pop_ts = now
        if oldest_ts <= 0.0 or oldest_ts > pop_ts:
            oldest_ts = pop_ts
        self._pstats.note_host_lag(pop_ts - oldest_ts, lane)
        self._pstats.note_device_lag(max(now - pop_ts, 0.0), lane)

    # -- latency quantile plane helpers (ISSUE 16) --------------------------

    @staticmethod
    def _qt_host(b) -> tuple | None:
        """Host snapshot of the bundle's DDSketch lanes (counts int64,
        zeros, total) for the harvest's quantile read. Caller must hold
        _bundle_mu when `b` is the live bundle (the next update donates
        its buffers)."""
        if b.quantiles is None:
            return None
        return (np.asarray(b.quantiles.counts).astype(np.int64).copy(),
                int(b.quantiles.zeros), int(b.quantiles.total))

    def _qt_value_lane(self, batch: EventBatch, vals: np.ndarray,
                       n: int) -> np.ndarray:
        """Fill `vals`, the staged rows of the block's value lane (row 4),
        from the configured wire column: saturate-cast to uint32 so
        magnitudes past 2^32-1 (~4.3s of latency) clamp into the top
        bucket span instead of wrapping back into the small buckets. Pad
        slots carry 0 (weight 0 anyway)."""
        raw = batch.cols[self._qt_field][:n].astype(np.uint64, copy=False)
        vals[:n] = np.minimum(raw, np.uint64(0xFFFFFFFF)).astype(np.uint32)
        vals[n:] = 0
        return vals

    def _qt_count(self, vals_np: np.ndarray | None, n: int) -> None:
        """Quantile-plane telemetry for one absorbed batch: every event
        enters the plane; those without a magnitude land in the zero
        bucket and are counted separately (gauge-discipline: both are
        monotonic counters)."""
        if not self._qt_on:
            return
        self._m_qt_events.inc(n)
        z = (n if vals_np is None
             else int(n - np.count_nonzero(vals_np[:n])))
        if z > 0:
            _tm_qt_zero.inc(z)

    # -- accuracy audit plane helpers (ISSUE 19) ----------------------------

    def _shadow_feed(self, keys: np.ndarray,
                     weights: np.ndarray | None = None) -> None:
        """Feed the real rows of one host batch into the run-scoped and
        window-scoped shadow samples. Host numpy only, off the device
        path; ShadowSample.update copies what it keeps, so passing a
        view of a pinned staging block is safe. Plane-off is one branch."""
        if self._shadow is None:
            return
        self._shadow.update(keys, weights)
        self._win_shadow.update(keys, weights)
        self._astats.note_fed(int(np.asarray(keys).size))

    # -- invertible plane helpers (ISSUE 15) --------------------------------

    @staticmethod
    def _padded_mntns(batch: EventBatch, n: int, pad: int) -> np.ndarray:
        """The batch's mntns column padded to the staged lane length
        (pad slots carry 0, which no tenant claims — weight 0 anyway)."""
        out = np.zeros(pad, dtype=np.uint64)
        out[:n] = batch.cols["mntns"][:n]
        return out

    def _inv_class_absorb(self, keys, mntns_np: np.ndarray,
                          w_np: np.ndarray) -> list:
        """Per-priority-class invertible updates for one batch. Class
        sketches stay single-chip like the history window plane, so
        summed per-class decodes reproduce whole-stream totals at any
        chip count. `keys` is the already-staged device array on the
        single-chip path (jnp.asarray is a no-op) and the host lane
        under sharding (the staged copy lives on another chip); the
        per-class weight vectors are host-computed tenant masks and pay
        the only new transfer. Run thread only; returns fence tokens (on
        CPU PJRT the restaged arrays may alias the pinned block)."""
        if not self._inv_classes:
            return []
        wts = class_weights([c for c, _ in self._inv_classes],
                            mntns_np, w_np)
        toks = []
        keys_d = jnp.asarray(keys)
        for i, ((c, s), w_c) in enumerate(zip(list(self._inv_classes),
                                              wts)):
            if not w_c.any():
                continue
            s2, tok = _inv_class_jit(s, keys_d, jnp.asarray(w_c))
            self._inv_classes[i] = (c, s2)
            toks.append(tok)
        return toks

    # the columnar hot path -------------------------------------------------

    def _note_arm(self, rows: int) -> tuple[str, Any]:
        """The arm the update step traces to at `rows` rows (the
        dispatcher's own rule, asked once a size) and its child of the arm
        counter, for the counter and the summary's pipeline block."""
        arm = self._arms.get(rows)
        if arm is None:
            name = update_arm(self.bundle, rows)
            arm = self._arms[rows] = (name, _tm_arm_steps.labels(
                gadget=self.ctx.desc.full_name, arm=name))
        return arm

    def _step_rows(self, n: int, cap: int) -> int:
        """Rows a batch of `n` events is staged and stepped at, in a block
        of `cap` rows: the smallest power of two that holds it, from
        STEP_ROWS_FLOOR up. A sharded round is rectangular and a batch is
        parked on its lane before the round's others are known, so under
        shard-ingest every batch keeps the whole block."""
        if self._shard_on:
            return cap
        return min(max(STEP_ROWS_FLOOR, 1 << (n - 1).bit_length()), cap)

    def pre_gadget_run(self) -> None:
        """Every size of the one-chip ladder compiled (or read from the
        persistent cache) before the source produces its first event: a
        size first met inside the run would put its compile on the loop
        thread. One step of each program the dispatch runs, on a block of
        zeros: weight 0 adds to no counter, is masked out of the HLL and
        becomes the empty key in the top-k, so the state stays what it was
        (the filler lanes of a flushed sharded round rest on the same
        property) and nothing is counted. The sharded step has one shape
        and compiles with the first round, as before (and a seal's programs
        with its first seal). The anomaly scorer's
        one program is primed at the slots that hold the containers the
        run is known to reach (`_expected_containers`; never fewer than
        CONTAINER_SLOTS_FLOOR), on a copy of the scorer (the step donates
        what it is given, and a step on the scorer itself would advance
        Adam's count); containers that appear beyond them double the slots
        inside the run, as before. With history on, the programs a seal
        dispatches (the bundle's snapshot, the window's capture and the
        opening of the next) are run once too, their outputs dropped."""
        if not self.enabled:
            return
        if self._anomaly_step is not None:
            self._hold_containers(self._expected_containers())
            self._primed_slots = len(self._container_counts)
            counts = np.zeros_like(self._container_counts)
            _scorer, scores = self._anomaly_step(
                jax.tree.map(jnp.array, self.scorer), counts,
                np.zeros(len(counts), np.float32))
            jax.block_until_ready(scores)
        if self._shard_on:
            return
        if self._hist_on:
            # what a seal dispatches, on the state as it stands: nothing
            # here is donated, and the outputs are dropped
            with self._bundle_mu:
                snap = _seal_snapshot_jit(self.bundle)
            cand = self._beside_planes(snap[1])
            jax.block_until_ready(
                (_window_capture_jit(self._wplanes, cand, np.int32(0)),
                 _window_open_jit(self._wplanes)) if self._event_time
                else _window_close_jit(self._wplanes, cand))
        rows = STEP_ROWS_FLOOR
        while rows <= self._pad and not self.ctx.done:
            z = jnp.asarray(np.zeros(rows, np.uint32))
            with self._bundle_mu:
                self.bundle, tok = _ingest_jit(
                    self.bundle, z, z, z, z, jnp.float32(0),
                    *((z,) if self._qt_on else ()))
                toks = [tok]
                for i, (c, s) in enumerate(self._inv_classes):
                    s, tok = _inv_class_jit(s, z, z)
                    self._inv_classes[i] = (c, s)
                    toks.append(tok)
            if self._hist_on:
                toks.append(self._window_step(
                    z, z, z, z, jnp.asarray(np.full(rows, LATE_AGE, np.uint8))
                    if self._event_time else self._age0))
            jax.block_until_ready(toks)
            rows *= 2

    def _staging_for(self, pad: int) -> tuple[PinnedBufferPool, H2DStager]:
        """The pinned pool + stager for the current pad shape; a pad
        growth (rare: one bigger batch) drains the old stager first so
        no in-flight block leaks the occupancy gauge. self._pad is
        ratcheted to the new shape so later normal-sized batches keep
        the grown pool instead of rebuilding it every flip."""
        if self._pool is None or self._pool.capacity != pad:
            if self._stager is not None:
                self._stager.drain()
            # 4 lanes: up to three distinct key columns + the weights
            # lane; the quantile plane adds a 5th (the value lane) —
            # plane-off runs keep the exact 4-lane pool
            self._pool = PinnedBufferPool(pad,
                                          lanes=5 if self._qt_on else 4,
                                          max_free=self._h2d_depth + 2)
            self._stager = H2DStager(self._pool, depth=self._h2d_depth,
                                     stats=self._pstats, wait=self._w_retire)
        self._pad = max(self._pad, pad)
        return self._pool, self._stager

    # -- multi-chip sharded ingest plane (ISSUE 14) -------------------------

    def _ensure_sharded(self) -> None:
        """Build the (node) mesh, the shard_map ingest/harvest jits, and
        the lane-stacked sharded bundle (lane 0 seeded with the resumed
        single-chip state so checkpoint-resume semantics hold)."""
        if self._sharded is not None:
            return
        from ..parallel.mesh import ingest_mesh
        self._mesh = ingest_mesh(self._chips)
        self._ingest_sharded = make_bundle_ingest_sharded(self._mesh,
                                                          self.bundle)
        self._harvest_sharded = make_bundle_harvest_sharded(self._mesh,
                                                            self.bundle)
        self._sharded = bundle_stack_sharded(self.bundle, self._mesh)
        g = self.ctx.desc.full_name
        self._m_rounds = {kind: _tm_shard_rounds.labels(gadget=g, kind=kind)
                          for kind in ("full", "flushed")}
        self._m_fillers = _tm_shard_fillers.labels(gadget=g)
        self._m_lane_events = [
            _tm_shard_lane_events.labels(gadget=g, lane=str(k))
            for k in range(self._chips)]
        self._pstats.shard_lanes(self._chips)

    def _lane_staging(self, pad: int) -> tuple[PinnedBufferPool, H2DStager]:
        """Pool + stager for the lane the NEXT batch lands on
        (_next_lane — the monotonic round-robin counter, untouched by
        concurrent flushes so assignment is a pure function of arrival
        order). Per-lane pinned pools carry the lane label; per-lane
        stagers pin their H2D to that lane's chip, so the transfer to
        chip k+1 overlaps compute on chip k. A pad growth flushes the
        open round at the OLD shape (rounds must be rectangular),
        drains, and rebuilds every lane."""
        self._ensure_sharded()
        if not self._lane_pools or self._lane_pools[0].capacity != pad:
            import jax
            with self._bundle_mu:
                self._flush_round_locked()
            for st in self._lane_stagers:
                st.drain()
            devices = list(self._mesh.devices.reshape(-1))
            self._lane_pools = [
                PinnedBufferPool(pad, lanes=5 if self._qt_on else 4,
                                 max_free=self._h2d_depth + 2, lane=k)
                for k in range(self._chips)]
            self._lane_stagers = [
                H2DStager(self._lane_pools[k], depth=self._h2d_depth,
                          device=devices[k], stats=self._pstats,
                          wait=self._w_retire)
                for k in range(self._chips)]
            # one cached zero lane per chip: the filler a flushed
            # partial round rides. Never donated (only the bundle is),
            # so it is reusable forever; keeping fillers OFF the pools/
            # stagers means the flush path (harvest/seal/checkpoint —
            # possibly another thread) never touches staging state the
            # capture thread mutates lock-free
            self._lane_zeros = [
                jax.device_put(np.zeros(pad, np.uint32), devices[k])
                for k in range(self._chips)]
        self._pad = max(self._pad, pad)
        return (self._lane_pools[self._next_lane],
                self._lane_stagers[self._next_lane])

    def _shard_absorb_locked(self, hh_d, distinct_d, dist_d, w_d,
                             new_drops: float, window_tokens: list,
                             slot: int, events: int, values_d=None) -> None:
        """Park one staged batch of `events` events on its lane (the
        staged arrays already live on that lane's chip; `slot` — captured
        at stage time — names the stager slot to fence at dispatch),
        count its events on the lane, and advance the
        round-robin counter; dispatch ONE sharded step when every lane
        holds a batch. Under the quantile plane each round carries a 5th
        value-lane array; a batch without one (folded source with no
        magnitude column) rides the lane's cached zero array — every
        event lands in the zero bucket, totals stay honest. Caller holds
        _bundle_mu (pending state and the sharded bundle move
        together)."""
        lane = self._next_lane
        if self._qt_on and values_d is None:
            values_d = self._lane_zeros[lane]
        arrays = (hh_d, distinct_d, dist_d, w_d)
        if self._qt_on:
            arrays = arrays + (values_d,)
        self._pending[lane] = {
            "arrays": arrays,
            "slot": slot,
            "drops": max(new_drops, 0.0),
            "fences": list(window_tokens),
        }
        self._m_lane_events[lane].inc(events)
        self._pstats.note_lane_events(lane, events)
        self._next_lane = (self._next_lane + 1) % self._chips
        if len(self._pending) >= self._chips:
            self._dispatch_round_locked()

    def _dispatch_round_locked(self) -> None:
        """Assemble the pending lanes' staged arrays into global
        node-sharded arrays (metadata only — the shards already live on
        their chips) and run the shard_map ingest step. Lanes with no
        pending batch (harvest/seal mid-round, ragged stream tails) ride
        a zero-weight filler block: weight 0 contributes to no sketch
        plane, so a flushed partial round folds exactly the batches it
        holds. Fillers are the cached per-lane zero arrays — no pool
        get, no staging, no stager state touched — so a flush from the
        checkpointer/harvest thread never races the capture thread's
        lock-free stage()/last_slot sequence. Each real batch is fenced
        on ITS stager slot (captured at stage time)."""
        if not self._pending:
            return
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..parallel.mesh import NODE_AXIS
        pad = self._lane_pools[0].capacity
        n_arr = 5 if self._qt_on else 4
        fillers = self._chips - len(self._pending)
        for lane in range(self._chips):
            if lane in self._pending:
                continue
            z = self._lane_zeros[lane]
            self._pending[lane] = {"arrays": (z,) * n_arr, "slot": None,
                                   "drops": 0.0, "fences": []}
        sh = NamedSharding(self._mesh, P(NODE_AXIS))
        by_lane = [self._pending[lane] for lane in range(self._chips)]

        def global_of(i):
            return jax.make_array_from_single_device_arrays(
                (self._chips, pad), sh,
                [p["arrays"][i].reshape(1, -1) for p in by_lane])

        hh, distinct, dist, w = (global_of(i) for i in range(4))
        devices = list(self._mesh.devices.reshape(-1))
        drops = jax.make_array_from_single_device_arrays(
            (self._chips,), sh,
            [jax.device_put(np.asarray([p["drops"]], np.float32),
                            devices[i])
             for i, p in enumerate(by_lane)])
        if self._qt_on:
            # the 5th lane array (per-event magnitudes) rides the same
            # sharded step; the sharded ingest maker added the values
            # argument when the bundle carries the plane
            self._sharded, tok = self._ingest_sharded(
                self._sharded, hh, distinct, dist, w, drops, global_of(4))
        else:
            self._sharded, tok = self._ingest_sharded(
                self._sharded, hh, distinct, dist, w, drops)
        for lane, p in enumerate(by_lane):
            # the global token waits for every lane's consumer (plus the
            # lane's window-plane steps) before its block recycles;
            # filler lanes (slot None) have no block to fence
            if p["slot"] is not None:
                self._lane_stagers[lane].fence_slot(
                    p["slot"], tuple([tok] + p["fences"]))
        self._pending = {}
        self._m_rounds["flushed" if fillers else "full"].inc()
        if fillers:
            self._m_fillers.inc(fillers)
        self._pstats.note_round(fillers)

    def _flush_round_locked(self) -> None:
        self._dispatch_round_locked()

    def _merged_locked(self):
        """The bundle every read path (harvest/seal/checkpoint/display)
        consumes: the live single-chip bundle, or — under shard-ingest —
        the collective harvest (psum/pmax + candidate re-rank) of the
        lane-stacked bundle, after flushing any partial round so every
        absorbed batch is visible. Bit-identical to the single-chip fold
        of the same stream (tests/test_sharded_ingest.py). Caller holds
        _bundle_mu."""
        if not self._shard_on or self._sharded is None:
            return self.bundle
        self._flush_round_locked()
        return self._harvest_sharded(self._sharded)

    def device_view(self) -> dict:
        """Read-only facts about the device side of this instance, taken
        under _bundle_mu (the ingest step donates the bundle, so a reader
        on another thread must not race it). chip_smoke.py and the
        sharded-ingest tests check these instead of the internals:

          lanes         ingest lanes: `chips` under shard-ingest, else 1
          lane_devices  each lane's device in lane order (the state's own
                        device until the sharded plane is built)
          state_shards  per leaf of the live state: [(device, shard shape)]
          staged        {lane: [device of each staged array]} for the
                        batches the open round has parked on their lanes
          step          (jitted ingest step, its arguments as
                        ShapeDtypeStructs): lower it to see what compiled.
                        The lanes are shown at the pad; on one chip the
                        same step also runs at the smaller sizes of the
                        ladder (`_step_rows`), sharded rounds at the pad
                        alone
          harvest       (jitted collective harvest, its arguments), None
                        while the state lives on one chip
        """
        def aval(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)

        with self._bundle_mu:
            sharded = self._sharded is not None
            state = self._sharded if sharded else self.bundle
            state_avals = jax.tree.map(aval, state)
            state_shards = [[(s.device, s.data.shape)
                             for s in leaf.addressable_shards]
                            for leaf in jax.tree.leaves(state)]
            staged = {lane: [next(iter(a.devices())) for a in p["arrays"]]
                      for lane, p in self._pending.items()}
            pad = self._pad
        if sharded:
            lane_devices = list(self._mesh.devices.reshape(-1))
            sh = state_avals.events.sharding   # P(node), any rank
            lane = jax.ShapeDtypeStruct((self._chips, pad), np.uint32,
                                        sharding=sh)
            drops = jax.ShapeDtypeStruct((self._chips,), np.float32,
                                         sharding=sh)
            step, harvest = self._ingest_sharded, (self._harvest_sharded,
                                                   (state_avals,))
        else:
            lane_devices = [state_shards[0][0][0]]
            lane = jax.ShapeDtypeStruct((pad,), np.uint32)
            drops = jax.ShapeDtypeStruct((), np.float32)
            step, harvest = _ingest_jit, None
        # keys x3, weights, drops, and the value lane under the quantile plane
        args = (state_avals,) + (lane,) * 4 + (drops,)
        if self._qt_on:
            args += (lane,)
        return {"lanes": self._chips if self._shard_on else 1,
                "lane_devices": lane_devices, "state_shards": state_shards,
                "staged": staged, "harvest": harvest, "step": (step, args)}

    def _staging(self, pad: int) -> tuple[PinnedBufferPool, H2DStager]:
        """Pool and stager of the batch about to land (its lane's under
        shard-ingest)."""
        return (self._lane_staging(pad) if self._shard_on
                else self._staging_for(pad))

    def enrich_batch(self, batch: EventBatch) -> None:
        """The fold adapter: an EventBatch's key columns folded into the
        lanes of a pinned block and staged, then `_absorb_staged`; the
        slices, the label sample and the anomaly distributions, which
        only an EventBatch's columns can give, ride along as its hooks."""
        if not self.enabled or batch.count == 0:
            return
        n = batch.count
        pad = self._pad
        while pad < n:
            pad *= 2
        rows = self._step_rows(n, pad)

        t0 = time.perf_counter()
        with self._st_fold:
            pool, stager = self._staging(pad)
            # the block keeps the pad's capacity; its first `rows` rows
            # are filled, staged and stepped (contiguous prefix views,
            # so the H2D copy shrinks with the step)
            block = pool.get()
            lanes: dict[str, np.ndarray] = {}

            def keys_for(colname: str) -> np.ndarray:
                lane = lanes.get(colname)
                if lane is None:
                    lane = block[len(lanes)][:rows]
                    a = batch.cols[colname][:n]
                    if a.dtype == np.uint64:
                        lane[:n] = fold64_to_32(a)
                    else:
                        lane[:n] = a
                    lane[n:] = 0
                    lanes[colname] = lane
                return lane

            hh = keys_for(self.hh_col)
            distinct = keys_for(self.distinct_col)
            dist = keys_for(self.dist_col)
            w = block[3][:rows]
            w[:n] = 1
            w[n:] = 0
            vals = (self._qt_value_lane(batch, block[4][:rows], n)
                    if self._qt_on else None)
            mntns = (self._padded_mntns(batch, n, rows)
                     if self._inv_classes else None)
            # pipeline watermarks: prefer the batch's stamped fields; an
            # unstamped batch with a real ts column recovers the oldest
            # event from it (one vectorized min)
            oldest = batch.oldest_ts
            if oldest <= 0.0:
                tmin = float(batch.cols["ts"][:n].min())
                if tmin > 0.0:
                    oldest = tmin / 1e9
        # the history window each event lands in: on the processing clock
        # the open one (a scalar age); on event time one age a row (a
        # narrow lane staged with the others) and the windows the batch's
        # slices go to. A jump of the event clock past what the planes hold
        # seals the oldest window first, in the seal's own stage
        age, groups = None, None
        if self._hist_on:
            ts = batch.cols["ts"][:n]
            span = None
            if self._event_time:
                with self._st_route:
                    span = int(ts.min()), int(ts.max())
                self._make_room(*span)
            with self._st_route:
                age, groups = self._route(ts, rows, span)
        # ONE async device put per distinct lane (shared columns stage
        # once); the transfer of this batch overlaps device compute of
        # the previous one — the block returns to the pool only after
        # the consumer fence completes
        with self._st_h2d:
            extra = [a for a in (vals, age) if a is not None]
            puts = stager.stage(block, list(lanes.values()) + [w] + extra)
            nk = len(lanes)
            by_col = dict(zip(lanes, puts))
            staged = (by_col[self.hh_col], by_col[self.distinct_col],
                      by_col[self.dist_col], puts[nk],
                      puts[nk + 1] if vals is not None else None,
                      puts[-1] if age is not None else self._age0)

        def late() -> None:
            # late enrichment (display-only work off the ingest path): two
            # vectorized slice writes park a small (k64, k32, comm) sample in
            # the rolling ring; name resolution happens at harvest/seal time
            self._label_sample(batch, hh, n)

        self._absorb_staged(
            stager, staged, (hh, distinct, dist, w, age), n, t0,
            drops=batch.drops, pop_ts=batch.pop_ts, oldest_ts=oldest,
            mntns=mntns, vals=vals,
            slices=lambda: self._accumulate_slices(batch, n, hh, distinct,
                                                   dist, groups),
            late=late,
            dists=((lambda: self._accumulate_container_dists(batch, n))
                   if self.anomaly_on else None))

    def ingest_folded(self, fb: FoldedBatch) -> None:
        """Zero-copy ingest of a pre-folded SoA batch (ig_source_pop_folded
        → PinnedBufferPool block): no EventBatch, no decode, no fold pass.
        The block must come from folded_block() — the stager returns it to
        this instance's pool once the update fence completes. The single
        keys lane feeds all three sketch streams (the folded fast path is
        for single-key-column gadgets; column-split gadgets take
        enrich_batch). The history window plane rides the same staged
        arrays, so sealed windows stay correct — but they carry NO
        subpopulation slices (the wire's kind column does not exist on
        the folded path) and no anomaly distributions; gadgets that need
        either must ingest through enrich_batch, and so must a run with
        event-time windows, which routes each event by its ts."""
        if not self.enabled or fb.count == 0:
            return
        if self._hist_on and self._event_time:
            raise ValueError("history-lateness routes each event by its ts, "
                             "which a folded batch does not carry: ingest "
                             "through enrich_batch")
        n = fb.count
        rows = self._step_rows(n, fb.capacity)
        t0 = time.perf_counter()
        with self._st_h2d:
            _pool, stager = self._staging(fb.capacity)
            # pop_folded2 filled row 3 with per-event magnitudes: the
            # value lane stages with the keys/weights in the same pinned
            # block (one more view, zero extra copies)
            keys, weights = fb.keys[:rows], fb.weights[:rows]
            vals = (fb.values[:rows]
                    if self._qt_on and fb.values is not None else None)
            host = (keys, weights) + (() if vals is None else (vals,))
            if n < rows:
                for lane in host:
                    lane[n:] = 0
            k_d, w_d, *v_d = stager.stage(fb.lanes, host)
        self._absorb_staged(
            stager, (k_d, k_d, k_d, w_d, v_d[0] if v_d else None,
                     self._age0),
            (keys, keys, keys, weights, None), n, t0, drops=fb.drops,
            pop_ts=fb.pop_ts,
            oldest_ts=fb.oldest_ts, mntns=fb.mntns[:rows], vals=vals)

    def _absorb_staged(self, stager: H2DStager, staged: tuple, host: tuple,
                       n: int, t0: float, *, drops: int, pop_ts: float,
                       oldest_ts: float, mntns: np.ndarray | None,
                       vals: np.ndarray | None, slices=None,
                       late=None, dists=None) -> None:
        """The one dispatch of a staged batch of `n` events, whichever
        adapter staged it (at `t0`): `staged` is the device arrays (hh,
        distinct, dist, weights, values or None, the window ages: a lane
        on event time, else a scalar or None) of the stager's last slot,
        `host` the host lanes (hh, distinct, dist, weights, ages or None)
        they were put from, `drops` the source's cumulative count. Their
        length is the rows the adapter chose (`_step_rows`), and every
        program here takes the shape it is given. On one chip the
        update is dispatched first and the window planes and class
        sketches ride the same staged arrays; under shard-ingest the
        staged arrays live on the batch's lane chip while those planes
        stay on chip 0, so they take the host lanes and the batch is
        parked on its lane after them, their tokens joining the round's
        fence. The stages are siblings: `ig:tpusketch_update` covers the
        step's dispatch only, so an idle gap under the window planes or
        the slices is named after them and not after the update."""
        hh_d, distinct_d, dist_d, w_d, v_d, age_d = staged
        hh, distinct, dist, w, age = host
        sharded = self._shard_on
        if self._hist_on:
            # a summary nearly due, or due in this turn: what can wait
            # (the seal worker's next step, the slices' backlog fold)
            # waits for the first turn behind it
            self._summary_near = near = (
                self._last_harvest + self.harvest_interval
                - time.monotonic()) <= self._seal_quiet
            if near:
                self._seal_clear.clear()
            else:
                self._seal_clear.set()
        slot = stager.last_slot
        lane = self._next_lane if sharded else 0
        new_drops = max(drops - self._drops_seen, 0)
        self._drops_seen = drops
        t1 = time.perf_counter()
        fence = []
        if not sharded:
            with self._st_update, self._bundle_mu:
                # the value lane is an argument only under the
                # quantile plane (None from a folded source without
                # one: the step zero-fills, every event lands in the
                # zero bucket, totals honest)
                self.bundle, tok = _ingest_jit(
                    self.bundle, hh_d, distinct_d, dist_d, w_d,
                    jnp.float32(new_drops),
                    *((v_d,) if self._qt_on else ()))
            fence.append(tok)
        fence += (self._window_planes(hh, distinct, dist, w,
                                      self._age0 if age is None
                                      else age,
                                      slices)
                  if sharded else
                  self._window_planes(hh_d, distinct_d, dist_d, w_d, age_d,
                                      slices))
        if self._inv_classes:
            # the staged keys are reused on one chip (only the
            # per-class WEIGHT vectors need a transfer). Under
            # _bundle_mu: _inv_class_jit donates, and the checkpointer
            # thread snapshots class state under the same lock
            with self._st_inv, self._bundle_mu:
                fence += self._inv_class_absorb(
                    hh if sharded else hh_d, mntns, w)
        if sharded:
            with self._st_update, self._bundle_mu:
                self._shard_absorb_locked(
                    hh_d, distinct_d, dist_d, w_d, float(new_drops),
                    fence, slot, n, values_d=v_d)
        else:
            # every consumer of the staged arrays is in the fence: the
            # pinned block is reused only once they all completed (on
            # CPU PJRT the device arrays may alias the host block, so
            # transfer-complete alone is not enough)
            with self._st_post:
                stager.fence(tuple(fence))
        t2 = time.perf_counter()
        with self._st_post:
            self._m_h2d.observe(t1 - t0)
            self._m_update.observe(t2 - t1)
            self._m_events.inc(n)
            self._m_steps.inc()
            rows = w.shape[0]
            self._m_step_rows.inc(rows)
            self._steps_by_rows[rows] = self._steps_by_rows.get(rows, 0) + 1
            self._arm, m_arm_steps = self._note_arm(rows)
            m_arm_steps.inc()
            self._qt_count(vals, n)
            if new_drops:
                self._m_drops.inc(new_drops)
            self._stats.steps += 1
            self._stats.events += n
            self._stats.drops = drops
            self._note_watermarks(pop_ts, oldest_ts, lane)
            # accuracy audit plane: the heavy-hitter lane's real rows feed
            # the shadow sample host-side, at the staged lane's weights
            self._shadow_feed(hh[:n], w[:n])
            if late is not None:
                late()
        if dists is not None:
            with self._st_dists:
                dists()
        # both read this batch boundary: the summary goes first, so that
        # not even a seal's capture stands in its way (history-interval 0
        # orders them so inside harvest())
        now = time.monotonic()
        if now - self._last_harvest >= self.harvest_interval:
            self._last_harvest = now
            self.harvest()
        if self._hist_on and self._hist_interval > 0:
            if self._event_time:
                self._advance_watermark()
            elif self._hist_clock() - self._win_start >= self._hist_interval:
                self._seal_at_boundary()

    def _window_planes(self, hh, distinct, dist, w, age, slices) -> list:
        """The history window's share of a batch: each event into the
        planes of its window (`window_planes_step`), so a seal reads
        window-only state, then the adapter's `slices`. Fed the staged
        arrays on one chip; under shard-ingest the host lanes, each
        distinct one put once on the default device (the age too where it
        is a lane). Returns the step's fence token (on CPU PJRT the
        restaged arrays may alias the pinned block)."""
        if not self._hist_on:
            return []
        if self._shard_on:
            with self._st_restage:
                put = {}

                def on_chip0(a):
                    if id(a) not in put:
                        put[id(a)] = jnp.asarray(a)
                    return put[id(a)]
                hh, distinct, dist, w = (on_chip0(a)
                                         for a in (hh, distinct, dist, w))
                if isinstance(age, np.ndarray):
                    age = jnp.asarray(age)
        with self._st_planes:
            tok = self._window_step(hh, distinct, dist, w, age)
        if slices is not None:
            with self._st_slices:
                slices()
        return [tok]

    def _window_step(self, hh, distinct, dist, w, age):
        """The window program on device arrays of one length (a batch's,
        or the zeros priming feeds); returns its fence token."""
        self._wplanes, tok = _window_step_jit(
            self._wplanes, hh, distinct, dist, w, age)
        return tok

    def folded_block(self) -> np.ndarray:
        """A pinned (4+, pad) staging block for pop_folded (rows 0..2 are
        the keys/weights/mntns lanes; row 3 is scratch unless the caller
        pops through `pop_folded(block, with_values=True)`, which fills
        it with per-event magnitudes for the quantile plane). Under
        shard-ingest the block comes from the pool of the lane the next
        ingest_folded will land on, so it recycles through that lane's
        ring."""
        return self._staging(self._pad)[0].get()

    # -- late enrichment (off the ingest path) ------------------------------

    def _label_sample(self, batch: EventBatch, hh: np.ndarray,
                      n: int) -> None:
        """Park up to 64 (k64, k32, comm) rows per batch in the rolling
        ring — pure slice writes, no per-row Python."""
        s = min(n, 64)
        raw = batch.cols[self.hh_col][:s]
        # only real 64-bit key hashes can be un-hashed through the vocab;
        # a widened uint32 column value would cost a guaranteed-miss
        # native lookup per key (and could alias a real vocab key), so
        # non-u64 columns park 0 and resolve falls through to comm
        is_hash = raw.dtype == np.uint64
        cap = self._lbl_cap
        i = self._lbl_i
        first = min(s, cap - i)
        self._lbl_k32[i:i + first] = hh[:first]
        self._lbl_k64[i:i + first] = raw[:first] if is_hash else 0
        if batch.comm is not None:
            self._lbl_comm[i:i + first] = batch.comm[:first]
        else:
            self._lbl_comm[i:i + first] = 0
        rem = s - first
        if rem:
            self._lbl_k32[:rem] = hh[first:s]
            self._lbl_k64[:rem] = raw[first:s] if is_hash else 0
            if batch.comm is not None:
                self._lbl_comm[:rem] = batch.comm[first:s]
            else:
                self._lbl_comm[:rem] = 0
        self._lbl_i = (i + s) % cap

    def _labels_as_found(self) -> tuple:
        """The label sample ring as a seal's finish reads it, maybe on
        another thread: copies of its lanes, and the vocabulary's answers
        for the rows the names cache does not cover, asked here in one
        native crossing. The finish must not call into the gadget itself:
        the run's teardown frees the sources' native handles before the
        operators' `post_gadget_run` can wait for the worker."""
        k32, k64 = self._lbl_k32.copy(), self._lbl_k64.copy()
        by_k64: dict[int, str] = {}
        bulk = getattr(self.gadget, "resolve_keys_bulk", None)
        if bulk is not None:
            known = np.fromiter(self._names, np.uint32, len(self._names))
            ask = np.unique(k64[(k64 != 0) & ~np.isin(k32, known)])
            by_k64 = dict(zip(ask.tolist(), bulk(ask)))
        return k32, k64, self._lbl_comm.copy(), by_k64.get

    def _resolve_late(self, keys32, ring: tuple | None = None,
                      names: dict[int, str] | None = None) -> None:
        """Resolve display names for (few) heavy-hitter keys from the
        sample ring — runs once per harvest/seal tick, never per batch.
        A key ABSENT from the ring is left unresolved (not cached as
        hex): it may age back into the ring on a later batch, and a
        cached placeholder would block resolution forever. A key found
        in the ring but yielding no vocab/comm name caches the hex
        fallback — that row really carried no name, matching the old
        per-batch behavior. The harvest reads the live ring into the live
        cache (loop thread); a seal's finish is handed what its capture
        took of both (`ring` from `_labels_as_found`, `names` a copy)."""
        lbl_k32, lbl_k64, lbl_comm, resolve = ring or (
            self._lbl_k32, self._lbl_k64, self._lbl_comm,
            getattr(self.gadget, "resolve_key", None))
        if names is None:
            names = self._names
        for k in keys32:
            k = int(k)
            if not k or k in names:
                continue
            j = np.flatnonzero(lbl_k32 == np.uint32(k))
            if not j.size:
                continue  # not sampled yet — retry next tick
            jj = int(j[0])
            k64 = int(lbl_k64[jj])
            name = ""
            if resolve is not None and k64:
                name = resolve(k64) or ""
            if not name:
                comm = bytes(lbl_comm[jj])
                name = comm.split(b"\0", 1)[0].decode("utf-8", "replace")
            names[k] = name or f"0x{k:08x}"

    def _expected_containers(self) -> int:
        """Containers the run is known to reach before its source starts:
        what the gadget says of its own stream (a synthetic source's
        `containers`), or the node's at attach as localmanager counted
        them. 0 where neither knows."""
        own = getattr(self.gadget, "expected_containers", None)
        return max(own() if own is not None else 0,
                   int(self.ctx.extra.get("containers_at_attach", 0)))

    def _hold_containers(self, containers: int) -> None:
        """Rows for `containers` slots: the power of two that holds them,
        doubled from what the array has (never shrunk). The one place the
        scorer's program changes shape."""
        counts = self._container_counts
        rows = len(counts)
        while rows < containers:
            rows *= 2
        if rows == len(counts):
            return
        grown = np.zeros((rows, counts.shape[1]), dtype=np.float32)
        grown[:len(counts)] = counts
        self._container_counts = grown
        self._m_anomaly_slots.set(rows)

    def _accumulate_container_dists(self, batch: EventBatch, n: int) -> None:
        mntns = batch.cols["mntns"][:n]
        keys = batch.cols[self.dist_col][:n]
        if self.anomaly_model == "seq":
            # per-container token *sequences* (order matters) for the LM
            from ..models.seqmodel import tokens_from_keys
            toks = tokens_from_keys(keys, self._ae_cfg.vocab)
            w = self._seq_window
            for ns in np.unique(mntns):
                seq = self._container_seqs.setdefault(int(ns), [])
                seq.extend(int(t) for t in toks[mntns == ns])
                if len(seq) > w:
                    del seq[:-w]
            return
        # one grouped pass: each event's slot behind the mntns -> slot
        # table, then one scatter-add into the flat [slots * dim] array
        dim = self._ae_cfg.input_dim
        slot = self._containers.slots_of(mntns)
        # containers the priming did not know of: the slots double
        self._hold_containers(len(self._containers))
        flat = slot * dim
        # dim is a power of two: key % dim
        flat += (keys & keys.dtype.type(dim - 1)).astype(np.intp)
        # the addend in the array's own type: a Python 1.0 is a float64,
        # which sends `ufunc.at` down its casting path, thirty times slower
        np.add.at(self._container_counts.reshape(-1), flat, np.float32(1.0))

    def container_distributions(self) -> tuple[list[int], np.ndarray]:
        """The containers seen (mntns, by slot) and a copy of their
        `[containers, dim]` count rows (`ae` / `vae`; loop thread, or
        after the run)."""
        ids = self._containers.ids()
        return ids, self._container_counts[:len(ids)].copy()

    def _seq_score_containers(self) -> dict[int, float] | None:
        """Train the sequence LM one step on all container windows and
        return per-container mean next-token NLL."""
        from ..models.seqmodel import seq_score, seq_train_step
        ready = {ns: s for ns, s in self._container_seqs.items() if len(s) >= 4}
        if not ready:
            return None
        # pad width to a power of two: bounds the set of compiled shapes
        w = max(len(s) for s in ready.values())
        w = min(1 << (w - 1).bit_length(), self._seq_window)
        rows = 1 << (len(ready) - 1).bit_length() if len(ready) > 1 else 1
        # filler rows stay all -1: fully-masked rows are loss-neutral (the
        # NLL denominators are clamped to 1) and their scores are dropped
        # by the zip truncation below
        mat = np.full((rows, w), -1, dtype=np.int32)
        for i, s in enumerate(ready.values()):
            mat[i, :len(s)] = s
        toks = jnp.asarray(mat)
        self.scorer, _ = seq_train_step(self.scorer, toks)
        scores = np.asarray(seq_score(self.scorer, toks))
        return {ns: float(s) for ns, s in zip(ready.keys(), scores)}

    # sketch history: sealed windows (history/) -----------------------------

    def _accumulate_slices(self, batch: EventBatch, n: int,
                           hh: np.ndarray, distinct: np.ndarray,
                           dist: np.ndarray, groups: list | None) -> None:
        """Hydra-lite subpopulation accumulation for the open windows:
        per-mntns (container/pod identity), per-kind (syscall), and the
        mntns×kind cross product. The events of each window (`groups`, from
        `_route`; None: the whole batch, into the one open window) are
        grouped once by their (mntns, kind) cell and absorbed by the
        window's array store (history/window.py WindowSlices); the
        per-mntns and per-kind slices are folds of the cells, taken at the
        seal. Bounded by history-max-slices; overflow is dropped AND
        accounted in the sealed window's header."""
        cols = [batch.cols["mntns"][:n], batch.cols["kind"][:n], hh[:n],
                distinct[:n],
                # one column for both streams is one lane, hashed once
                None if self.dist_col == self.distinct_col else dist[:n]]
        for k, sel in groups or [(0, None)]:
            store = self._win_slices.get(k)
            if store is None:
                store = self._win_slices[k] = self._slices_cls(
                    self._hist_max_slices)
            store.absorb(*(c if sel is None or c is None else c[sel]
                           for c in cols), fold=not self._summary_near)

    # event-time windows ------------------------------------------------------

    def _make_room(self, oldest: int, newest: int) -> None:
        """Before a batch whose events span [oldest, newest] (ns): where
        its newest event's window would outnumber the planes beside the
        open ones (a jump of the event clock), the oldest seal early."""
        if self._ev_lo is None:             # the first batch: none late
            self._ev_lo = oldest // self._win_ns
            self._ev_hi = self._ev_lo - 1
        else:                               # where the source was idle
            self._advance_watermark(batch=False)
        while newest // self._win_ns - self._ev_lo >= self._wplanes.n_open:
            self._seal_oldest()

    def _route(self, ts: np.ndarray, rows: int,
               span: tuple[int, int] | None) -> tuple:
        """The windows of a batch's events. On the processing clock every
        event is in the open window: (None, None), a scalar age and
        nothing to stage. On event time each event's window is that of its
        own `ts` (`span`: the batch's oldest and newest, ns), against the
        watermark as it stood before this batch (`_advance_watermark`
        moves it after), so an event delayed by at most history-lateness
        is never late; the windows the batch reaches are opened. Returns
        the age lane of `rows` rows (0 = the newest open window; LATE_AGE
        for an event older than the oldest open window, and for pad rows)
        and the windows the events went to: [(window, their rows, or None
        for all of them)]."""
        if span is None:
            return None, None
        oldest, newest = span
        width = self._win_ns
        n = len(ts)
        self._ev_batch_newest = newest
        kmax = newest // width
        self._ev_hi = max(self._ev_hi, self._ev_lo - 1)
        while self._ev_hi < kmax:
            self._wplanes = _window_open_jit(self._wplanes)
            self._ev_hi += 1
        lo, hi = self._ev_lo, self._ev_hi
        self._m_open_windows.set(hi - lo + 1)
        age = np.full(rows, LATE_AGE, np.uint8)
        if oldest >= hi * width:            # all of it in the newest window
            age[:n] = 0
            self._win_events[hi] = self._win_events.get(hi, 0) + n
            return age, [(hi, None)]
        a = age[:n]
        a[:] = 0
        for j in range(1, hi - lo + 1):
            a += ts < np.uint64((hi - j + 1) * width)
        late = ts < np.uint64(lo * width)
        a[late] = LATE_AGE
        groups = []
        for j in range(hi - lo + 1):
            sel = np.flatnonzero(a == j)
            if len(sel):
                groups.append((hi - j, sel))
                self._win_events[hi - j] = (
                    self._win_events.get(hi - j, 0) + len(sel))
        nlate = int(np.count_nonzero(late))
        if nlate:
            self._late_pending += nlate
            self._late_total += nlate
            self._m_late_events.inc(nlate)
        return age, groups

    def _watermark_ns(self) -> int:
        """The newest event time absorbed less history-lateness. Where no
        batch has come for a whole window, it moves on from there with the
        clock by the idle time past that window, so an idle node's last
        windows seal; a source that keeps sending is held to its own event
        time, however far it runs behind the clock."""
        idle = round((self._hist_clock() - self._ev_batch_at) * 1e9)
        return (self._ev_newest_ns + max(idle - self._win_ns, 0)
                - self._late_ns)

    def _advance_watermark(self, batch: bool = True) -> None:
        """The watermark moves, and every open window whose end it has
        passed seals, oldest first: after a batch (by its newest event),
        and before one and on an idle turn (by the idle time alone)."""
        if batch:
            self._ev_newest_ns = max(self._ev_newest_ns,
                                     self._ev_batch_newest)
            self._ev_batch_at = self._hist_clock()
        wm = self._watermark_ns()
        width = self._win_ns
        while (self._ev_lo + 1) * width <= wm:
            if self._ev_lo > self._ev_hi:   # none open: skip to the mark
                self._ev_lo = max(self._ev_lo, wm // width)
                self._ev_hi = self._ev_lo - 1
                break
            self._seal_oldest()
        self._m_open_windows.set(max(self._ev_hi - self._ev_lo + 1, 0))

    def idle(self) -> None:
        """The gadget's loop found no event on this turn: on event time an
        idle source's watermark follows the clock (`_watermark_ns`)."""
        if (self.enabled and self._hist_on and self._event_time
                and self._ev_lo is not None):
            self._advance_watermark(batch=False)

    def _seal_oldest(self) -> None:
        """The served path's seal of the oldest open event-time window,
        and the watermark's window moved past it."""
        if self._ev_lo <= self._ev_hi:
            self._seal_at_boundary(self._ev_lo)
        self._ev_lo += 1

    # sealing ---------------------------------------------------------------

    def seal_window(self) -> None:
        """Seal the open window into the history store (on event time
        every open window, oldest first): ONE frame, ONE O_APPEND write
        each (a kill mid-seal tears at most this window, and the torn tail
        is dropped-and-accounted on read). Empty windows (no events since
        the last seal, and no late events to carry) are skipped — they
        carry no state and would bloat the range index. Synchronous: whatever the seal worker still holds
        is finished first, and the windows are in the store (and
        announced) when this returns."""
        with self._st_seal:
            self._seal_drain()
            if not self._event_time:
                windows = [None]
            elif self._ev_lo is None:
                windows = []
            else:
                windows = list(range(self._ev_lo, self._ev_hi + 1))
                self._ev_lo = self._ev_hi + 1
                self._m_open_windows.set(0)
            for k in windows:
                cap = (self._capture_window() if k is None
                       else self._capture_window(k))
                if cap is not None:
                    self._finish_window(cap, "caller")

    def _seal_at_boundary(self, window: int | None = None) -> None:
        """The served path's seal (of the processing-time window, or of
        event-time `window`): the capture here, on the loop thread, the
        finish on a worker thread. No queue: a boundary that finds the
        worker still on the window before waits for it (counted), so at
        most one window is pending and windows reach the store in order.
        The stage times the wait and the capture: the stall a seal puts on
        the loop."""
        with self._st_seal:
            if self._seal_drain():
                self._seal_stats["waited"] += 1
                self._m_seal_waits.inc()
            cap = (self._capture_window() if window is None
                   else self._capture_window(window))
            if cap is not None:
                due = (cap.end_ts if self._event_time
                       else cap.start_ts + self._hist_interval)
                self._m_seal_delay.observe(
                    max(self._hist_clock() - due, 0.0))
                self._seal_thread = threading.Thread(
                    target=self._finish_on_worker, args=(cap,), daemon=True,
                    name=f"tpusketch-seal-{self.ctx.run_id}")
                self._seal_thread.start()

    def _seal_drain(self) -> bool:
        """Wait for the window the seal worker holds, if any (with its
        gate open: the thread that waits here turns no batch meanwhile).
        Says whether there was one to wait for."""
        t = self._seal_thread
        if t is None:
            return False
        busy = t.is_alive()
        if busy:
            self._seal_clear.set()
        with self._w_join:
            t.join()
        self._seal_thread = None
        return busy

    def _beside_planes(self, cand):
        """The snapshot's candidate keys where the window planes live:
        under shard-ingest the merged bundle is replicated over the mesh
        and the window planes stay on chip 0."""
        if not self._shard_on:
            return cand
        return jax.device_put(cand, self._wplanes.count.sharding)

    def _capture_window(self, window: int | None = None
                        ) -> _WindowCapture | None:
        """A seal's first half, on the thread that owns the live state:
        everything that reads or swaps it, and nothing else. Small
        programs dispatched, no read-back, no sort, no file. `window` is
        the event-time window's index (None: the processing-time window,
        which this capture closes and the next opens). None for an empty
        window."""
        if self._event_time:
            start = window * self._win_ns / 1e9
            end = (window + 1) * self._win_ns / 1e9
            age, key = self._ev_hi - window, window
            slices = self._win_slices.pop(key, None)
            if not self._win_events.pop(key, 0) and not slices and \
                    not self._late_pending:
                return None
        else:
            start, end, age, key = self._win_start, self._hist_clock(), 0, 0
            host_events = self._stats.events
            slices = self._win_slices.pop(key, None)
            if host_events == self._win_host_events and not slices:
                self._win_start = end
                return None
            self._win_start = end
            self._win_host_events = host_events
        with self._bundle_mu:
            # under shard-ingest the flush of the open round and the
            # collective harvest: they are the state
            snap = _seal_snapshot_jit(self._merged_locked())
        # window-only state: the window's ring slot is its CMS;
        # candidates re-estimated against it give the window top-k
        cand = self._beside_planes(snap[1])
        if self._event_time:
            planes = _window_capture_jit(self._wplanes, cand, np.int32(age))
        else:
            planes, self._wplanes = _window_close_jit(self._wplanes, cand)
        self._win_n += 1
        # accuracy audit plane: the WINDOW-scoped shadow sample rides the
        # sealed window (copies: the live sample resets here)
        shadow = None
        if self._win_shadow is not None:
            shadow = (self._win_shadow.keys.copy(),
                      self._win_shadow.weights.copy(),
                      int(self._win_shadow.capacity))
            self._win_shadow.reset()
        late, self._late_pending = self._late_pending, 0
        return _WindowCapture(
            window=self._win_n, start_ts=float(start), end_ts=float(end),
            snap=snap, cms=planes[0], counts=planes[1], hll=planes[2],
            ent=planes[3], events=planes[4], late=late,
            slices=slices or self._slices_cls(self._hist_max_slices),
            shadow=shadow,
            # the names a sealed window carries are resolved from the
            # sample ring and the cache as this boundary found them
            labels=self._labels_as_found(), names=dict(self._names))

    @staticmethod
    def _snapshot_host(snap: tuple) -> tuple:
        """A `_seal_snapshot` as the host values window deltas are taken
        of: one blocking read of its arrays."""
        drops, cand, overflow, inv, qt = jax.device_get(snap)
        if inv is not None:
            inv = (inv[0].astype(np.int64), inv[1], inv[2])
        if qt is not None:
            qt = (qt[0].astype(np.int64), int(qt[1]), int(qt[2]))
        # the candidate-overflow latch rides along: an overflowed run's
        # windows carry approx=True, so merged and historical answers stay
        # tainted across the seal boundary
        return float(drops), cand, bool(int(overflow)), inv, qt

    def _finish_on_worker(self, cap: _WindowCapture) -> None:
        """The seal worker's thread: one window, then it ends. It opens no
        turn stage and no `ig:` annotation (it is no part of a turn): on
        the profiler's clock its finish is `worker:seal_finish`, a name
        the benchmark's reduction does not take for the loop's. Its CPU
        time goes to the harvest interval it ends in. A window it fails
        on is counted and logged like a failed append."""
        cpu0 = time.thread_time_ns()
        cls = annotation_class()
        ann = cls("worker:seal_finish") if cls.is_enabled() else None
        try:
            self._finish_window(cap, "worker")
        except Exception:  # noqa: BLE001 — nobody joins this thread for a result
            from ..history import HISTORY_METRICS
            HISTORY_METRICS.drops.labels(reason="seal").inc()
            _ckpt_log.warning("window seal failed (window %d was dropped)",
                              cap.window, exc_info=True)
        finally:
            if ann is not None:
                ann.__exit__(None, None, None)
            self._worker_cpu.append(time.thread_time_ns() - cpu0)

    def _seal_step(self, finish: str) -> None:
        """Between the steps of a finish: the worker waits here while the
        loop thread has a summary nearly due (`SEAL_QUIET_S`). A loop that
        stopped turning holds it a second at most; a caller's finish never
        waits."""
        if finish == "worker":
            self._seal_clear.wait(1.0)

    def _finish_window(self, cap: _WindowCapture, finish: str) -> None:
        """A seal's second half, on the caller's thread or the worker's:
        everything that touches no live state. Blocks on the capture's
        arrays, takes the deltas against the window before, sorts the
        candidates, folds the slices, digests, appends, announces."""
        from ..history import HISTORY, SealedWindow, window_digest
        t0 = time.perf_counter()
        self._seal_step(finish)
        now = self._snapshot_host(cap.snap)
        base, self._win_base = self._win_base, now
        drops0, _cand0, _overflow0, inv0, qt0 = base
        drops, cand, overflow, inv_now, qt_now = now
        cms, counts, hll, ent, win_events = jax.device_get(
            (cap.cms, cap.counts, cap.hll, cap.ent, cap.events))
        win_events = int(win_events)
        counts = counts.astype(np.int64)
        order = np.argsort(-counts)
        keep = [(int(cand[i]), int(counts[i])) for i in order
                if cand[i] != 0 and counts[i] > 0]
        names = cap.names
        self._resolve_late([k for k, _ in keep[:32]], cap.labels, names)
        # invertible plane rides the window as a cumulative-state DELTA:
        # the lanes are pure adds, so subtraction is exact (uint32 wrap
        # included) and merged windows decode like merged live state
        inv_kw = {}
        if inv_now is not None and inv0 is not None:
            inv_kw = {
                "inv_count": (inv_now[0] - inv0[0]).astype(np.int32),
                "inv_keysum": inv_now[1] - inv0[1],
                "inv_fpsum": inv_now[2] - inv0[2],
            }
        # DDSketch quantile plane rides the same cumulative-delta recipe:
        # bucket counts / zeros / total are pure integer adds, so the
        # window's latency distribution is an exact subtraction — merged
        # windows fold via dd_merge like merged live state
        if qt_now is not None and qt0 is not None:
            inv_kw.update(
                qt_counts=(qt_now[0] - qt0[0]).astype(np.int32),
                qt_zeros=int(qt_now[1] - qt0[1]),
                qt_total=int(qt_now[2] - qt0[2]),
                qt_alpha=float(self._qt_alpha),
                qt_min_value=float(self._qt_minv),
            )
        # plane-off runs add no shadow keys to the frame or the digest
        if cap.shadow is not None:
            inv_kw.update(rs_keys=cap.shadow[0], rs_weights=cap.shadow[1],
                          rs_capacity=cap.shadow[2])
        self._seal_step(finish)
        slices = cap.slices.seal()
        self._seal_step(finish)
        win = SealedWindow(
            gadget=self._hist_gadget,
            node=self.ctx.extra.get("node", "") or "",
            run_id=self.ctx.run_id,
            window=cap.window,
            start_ts=cap.start_ts,
            end_ts=cap.end_ts,
            events=win_events,
            drops=int(drops - drops0),
            cms=cms.astype(np.int32),
            hll=hll.astype(np.int32),
            ent=ent.astype(np.float32),
            topk_keys=np.array([k for k, _ in keep], dtype=np.uint32),
            topk_counts=np.array([c for _, c in keep], dtype=np.int64),
            slices=slices,
            names={k: names[k] for k, _ in keep if k in names},
            slices_dropped=cap.slices.dropped,
            approx=overflow,
            late=cap.late,
            **inv_kw,
        )
        # what this finish resolved goes back to the cache the harvests
        # and the next capture read (one C-level update of a dict)
        self._names.update(names)
        win.digest = window_digest(win)
        self._seal_step(finish)
        try:
            with self._span("tpusketch/seal-window", window=cap.window,
                            events=win_events):
                HISTORY.append_window(win, writer=self._hist_writer)
        except (OSError, ValueError) as e:
            if not isinstance(e, OSError):
                # an OSError was already counted by the writer's append
                # path (reason="append"); counting it again here would
                # report two lost windows for one failure
                from ..history import HISTORY_METRICS
                HISTORY_METRICS.drops.labels(reason="seal").inc()
            _ckpt_log.warning("window seal failed (window %d kept in "
                              "memory was dropped): %r", cap.window, e)
        else:
            # announce the sealed window on the run stream (header only,
            # no payload): summary-tier subscribers learn it exists and
            # can FetchWindows it without ever riding the raw batches
            hook = self.ctx.extra.get("on_window_sealed")
            if hook is not None:
                try:
                    hook({"gadget": win.gadget, "window": win.window,
                          "start_ts": win.start_ts, "end_ts": win.end_ts,
                          "events": win.events, "drops": win.drops,
                          "late": win.late, "digest": win.digest})
                except Exception as he:  # noqa: BLE001 — announce only
                    _ckpt_log.warning("window announce failed: %r", he)
            # standing queries fold the window ONLY after a successful
            # append: the engine's coverage must never include a window
            # the store dropped, or a cache hit would disagree with the
            # ad-hoc recompute over what's actually fetchable
            if self._sq_engine is not None:
                try:
                    pubs = self._sq_engine.on_seal(win, now=cap.end_ts)
                except Exception as qe:  # noqa: BLE001 — observe only
                    _ckpt_log.warning("standing-query refresh failed: "
                                      "%r", qe)
                    pubs = []
                qhook = self.ctx.extra.get("on_query_answer")
                if qhook is not None:
                    for qheader, qpayload in pubs:
                        try:
                            qhook(qheader, qpayload)
                        except Exception as qe:  # noqa: BLE001
                            _ckpt_log.warning(
                                "query answer publish failed: %r", qe)
        if self._hist_engine is not None:
            # time-gated background pass: sealed segments whose windows
            # aged past their level's horizon fold into super-windows
            # (the active segment — where this window just landed — is
            # never touched)
            try:
                self._hist_engine.maybe_compact(self._hist_writer.path)
            except (OSError, ValueError) as e:
                _ckpt_log.warning("compaction pass failed: %r", e)
        self._last_slices = {"slices": len(cap.slices),
                             "cells": cap.slices.cells,
                             "hh_entries": cap.slices.hh_entries,
                             "admitted": len(cap.slices),
                             "dropped": cap.slices.dropped}
        self._m_slice_hh.inc(cap.slices.hh_entries)
        self._m_slices["admitted"].inc(len(cap.slices))
        self._m_slices["dropped"].inc(cap.slices.dropped)
        took = time.perf_counter() - t0
        self._m_seals[finish].inc()
        self._m_seal_finish_s.observe(took)
        stats = self._seal_stats
        stats[finish] += 1
        stats["finish_ms_last"] = took * 1e3
        stats["finish_ms_max"] = max(stats["finish_ms_max"], took * 1e3)

    # harvest ---------------------------------------------------------------

    def harvest(self) -> SketchSummary:
        with self._span("tpusketch/harvest", epoch=self._epoch + 1):
            t0 = time.perf_counter()
            merged = None
            if self._sharded is not None:
                # what sharding puts before a harvest, as a sibling stage:
                # the flush of the open round and the dispatch of the
                # collective harvest. Its output is a fresh bundle no step
                # donates, so the lock is let go before the digest
                with self._st_merge, self._bundle_mu:
                    merged = self._merged_locked()
            with self._st_harvest:
                summary = self._harvest_traced(t0, merged)
            if self._hist_on and self._hist_interval <= 0:
                # history-interval 0: one sealed window per harvest — the
                # deterministic-replay mode (harvest boundaries are
                # recorded EV_SUMMARY records, so replay reseals identical
                # windows). After the harvest stage, as its sibling
                self.seal_window()
        return summary

    def _harvest_traced(self, t0: float, merged=None) -> SketchSummary:
        # one packed digest: a single blocking D2H read per tick, not 6;
        # dispatched under the
        # bundle lock so a concurrent update can't donate the buffers
        # mid-read. Under shard-ingest harvest() hands in `merged`, the
        # collective harvest of the flushed lanes — same digest, any
        # chip count. The invertible decode's DEVICE loop dispatches
        # under the same lock (its outputs are fresh buffers, and the
        # dispatched computation pins its inputs against later donation);
        # the numpy finisher runs outside it.
        inv_dev = None
        qt_now = None
        with self._bundle_mu:
            if merged is None:
                merged = self.bundle
            digest = bundle_digest_jit(merged)
            if self._inv_on and merged.inv is not None:
                from ..ops.invertible import inv_decode_device
                cap = min(4096, inv_capacity(self._inv_rows, self._inv_lb))
                inv_dev = inv_decode_device(merged.inv, sweeps=2, cap=cap)
            if self._qt_on and merged.quantiles is not None:
                # snapshot under the lock (single-chip: the next update
                # donates these buffers); the quantile math runs on the
                # host copies outside it
                qt_now = self._qt_host(merged)
            scores_d = None
            if self._anomaly_step is not None and len(self._containers):
                # the scorer's one program, dispatched behind the digest so
                # the device runs both while the host waits once; under the
                # lock because the step donates the scorer the
                # checkpointer reads. The counts go over as they stand (the
                # scores are read back below, before the next batch adds
                # to them)
                t_score = time.perf_counter_ns()
                rows = self._container_counts
                live = np.zeros(len(rows), np.float32)
                live[:len(self._containers)] = 1.0
                self.scorer, scores_d = self._anomaly_step(
                    self.scorer, rows, live)
                self._turn.note_anomaly_score(
                    time.perf_counter_ns() - t_score)
        # the one blocking read of the tick, counted apart from the stage
        with self._w_harvest:
            events_f, drops_f, distinct, entropy_bits, approx, keys, \
                counts = decode_digest(digest)
        anomaly = None
        if self.anomaly_on:
            t_score = time.perf_counter_ns()
            if self.anomaly_model == "seq":
                anomaly = self._seq_score_containers()
            elif scores_d is not None:
                # filler rows' scores fall off the end of the zip
                anomaly = dict(zip(self._containers.ids(),
                                   np.asarray(scores_d).tolist()))
                self._anomaly_steps += 1
                self._m_anomaly_steps.inc()
            self._turn.note_anomaly_score(time.perf_counter_ns() - t_score)
        if approx and not self._overflow_counted:
            # count RUNS that crossed into approximation, not harvests:
            # the flag is latched, so one inc per instance is the honest
            # cardinality
            self._overflow_counted = True
            _tm_cand_overflow.labels(gadget=self.ctx.desc.full_name).inc()
        order = np.argsort(-counts)
        hh = [(int(keys[i]), int(counts[i])) for i in order if keys[i] != 0]
        # invertible plane: decode the merged state → exact (key, count)
        # pairs, plus the keys the candidate ring MISSED (satellite 2's
        # observable win: e.g. a key heavy only fleet-wide)
        decoded: list[tuple[int, int]] = []
        decoded_only: list[tuple[int, int]] = []
        inv_info = None
        classes_out = None
        if inv_dev is not None:
            from ..ops.invertible import inv_decode_finish
            dec = inv_decode_finish(*inv_dev)
            # the FULL recovery rides the in-process summary: the alert
            # engine builds one heavy_flow state machine per decoded key
            # and a truncation here would starve keys past the cut (and
            # flap the boundary key); the wire codec caps what it ships
            decoded = dec.keys
            ring = {k for k, _ in hh}
            decoded_only = [(k, c) for k, c in dec.keys if k not in ring]
            inv_info = {"recovered": dec.recovered,
                        "complete": dec.complete,
                        "residual_events": dec.residual_events,
                        "capacity": inv_capacity(self._inv_rows,
                                                 self._inv_lb)}
            if self._inv_classes:
                # snapshot under the lock (the next class update donates
                # these buffers), decode on the host copies outside it
                with self._bundle_mu:
                    cls_snap = [
                        (c, (np.asarray(s.count), np.asarray(s.keysum),
                             np.asarray(s.fpsum)))
                        for c, s in self._inv_classes]
                classes_out = {}
                for c, arrs in cls_snap:
                    cdec = inv_decode(arrs)
                    classes_out[c.name] = {
                        "tenants": (list(c.tenants)
                                    if c.tenants is not None else "*"),
                        "log2_buckets": c.log2_buckets,
                        "capacity": inv_capacity(self._inv_rows,
                                                 c.log2_buckets),
                        "decoded": cdec.top(32),
                        "recovered": cdec.recovered,
                        "complete": cdec.complete,
                        "residual_events": cdec.residual_events,
                    }
        # latency quantile read: four ranks off the merged DDSketch row,
        # plus the accounting a reader needs to judge them (zeros = no
        # magnitude; underflow = clamped below min_value into bucket 0)
        qt_out = None
        if qt_now is not None:
            from ..ops.quantiles import dd_quantile_np
            c, z, t = qt_now
            if t > 0:
                ps = dd_quantile_np(c, z, t, [0.50, 0.90, 0.99, 0.999],
                                    alpha=self._qt_alpha,
                                    min_value=self._qt_minv)
            else:
                ps = np.zeros(4)   # empty sketch: 0.0, never NaN on wire
            qt_out = {
                "p50": float(ps[0]), "p90": float(ps[1]),
                "p99": float(ps[2]), "p999": float(ps[3]),
                "zeros": int(z), "total": int(t),
                "underflow": int(c[0]), "alpha": float(self._qt_alpha),
            }
        self._close_interval()
        # pipeline health plane: snapshot the per-stage lag/starvation
        # accounting and render one span per stage under this harvest's
        # span — export_chrome then shows a real pipeline timeline with
        # watermarks/quantiles in the span args (run/trace IDs thread
        # through the ambient harvest context)
        pipe_out = self._pstats.snapshot()
        pipe_out["update_arm"] = self._arm
        # steps by the rows they ran at (string keys: the block rides JSON)
        pipe_out["step_rows"] = {str(rows): steps for rows, steps
                                 in sorted(self._steps_by_rows.items())}
        if self._hist_on:
            if self._last_slices is not None:
                # what the last sealed window's slice store held
                pipe_out["slices"] = dict(self._last_slices)
            # who finished the windows so far, the boundaries that waited
            # for the worker, and whether it holds a window now
            worker = self._seal_thread
            pipe_out["seal"] = {
                **self._seal_stats,
                "pending": int(worker is not None and worker.is_alive())}
            # the windows open now, how far the watermark trails the clock
            # (0 on the processing clock) and the late events so far
            lag = 0.0
            if self._event_time and self._ev_lo is not None:
                lag = self._hist_clock() - self._watermark_ns() / 1e9
            pipe_out["windows"] = {
                "open": (max(self._ev_hi - self._ev_lo + 1, 0)
                         if self._event_time and self._ev_lo is not None
                         else int(not self._event_time)),
                "watermark_lag_s": lag, "late": self._late_total}
        if self._anomaly_step is not None:
            pipe_out["anomaly"] = {"steps": self._anomaly_steps,
                                   "containers": len(self._containers),
                                   "slots": len(self._container_counts),
                                   "primed_slots": self._primed_slots}
        for stage, row in pipe_out["stages"].items():
            with self._span(f"tpusketch/stage/{stage}",
                            watermark_s=row["watermark_s"],
                            p50_s=row["p50_s"], p99_s=row["p99_s"],
                            count=row["count"]):
                pass
        if pipe_out["starved"] or pipe_out["saturated"]:
            with self._span("tpusketch/stage/stager",
                            starved=pipe_out["starved"],
                            saturated=pipe_out["saturated"],
                            starved_ratio=pipe_out["starved_ratio"],
                            stall_s=pipe_out["stall_s"]):
                pass
        # accuracy audit plane (ISSUE 19): per-stat analytic envelopes
        # from the live geometry + observed mass, with OBSERVED error vs
        # the run-scoped shadow sample. Plane-off harvests carry
        # accuracy=None — wire headers and digests stay byte-identical
        acc_out = None
        if self._shadow is not None:
            from ..ops.accuracy import accuracy_block
            depth, width = self.bundle.cms.table.shape
            acc_out = accuracy_block(
                events=float(events_f),
                depth=int(depth), width=int(width),
                hll_p=int(np.log2(max(
                    self.bundle.hll.registers.shape[0], 2))),
                ent_log2_width=int(np.log2(max(
                    self.bundle.entropy.counts.shape[0], 2))),
                distinct=float(distinct),
                entropy_bits=float(entropy_bits),
                hh_keys=np.array([k for k, _ in hh], dtype=np.uint32),
                hh_counts=np.array([c for _, c in hh], dtype=np.int64),
                qt_alpha=(float(self._qt_alpha) if self._qt_on else None),
                shadow=self._shadow,
            )
            self._astats.observe_block(acc_out)
        # late enrichment: names resolve HERE (once per tick, from the
        # sample ring), not in the per-batch ingest path
        self._resolve_late([k for k, _ in hh[:32]])
        self._epoch += 1
        summary = SketchSummary(
            events=int(events_f),
            drops=int(drops_f),
            distinct=distinct,
            entropy_bits=entropy_bits,
            heavy_hitters=hh,
            anomaly=anomaly,
            epoch=self._epoch,
            names={k: self._names[k] for k, _ in hh if k in self._names},
            approx=approx,
            decoded=decoded,
            decoded_only=decoded_only,
            inv=inv_info,
            classes=classes_out,
            quantiles=qt_out,
            pipeline=pipe_out,
            accuracy=acc_out,
        )
        # read the consumer LIVE from ctx.extra (falling back to the one
        # captured at init): the alerts operator chains its engine into
        # the summary path by swapping this key, and instantiation order
        # between operators must not decide whether detection happens
        cb = self.ctx.extra.get("on_sketch_summary", self.on_summary)
        if cb is not None:
            cb(summary)
        self._m_harvests.inc()
        self._m_harvest_s.observe(time.perf_counter() - t0)
        return summary

    def _close_interval(self) -> None:
        """This harvest closes the loop's interval (telemetry/pipeline.py
        TurnClock.close_interval) as the summary leaves, where the
        benchmark times it: the summaries its gap missed, counted with
        their cause, and whether the source shed inside it."""
        shed = self._drops_seen - self._iv_drops
        worker = 0
        while self._worker_cpu:
            worker += self._worker_cpu.popleft()
        rec = self._turn.close_interval(self.harvest_interval, shed,
                                        worker * 1e-9)
        if rec is None:
            return
        self._iv_drops = self._drops_seen
        if rec["missing"]:
            self._m_late[rec["cause"]].inc(rec["missing"])
        if shed:
            self._m_shed_intervals.inc()

    def post_gadget_run(self) -> None:
        if self.enabled:
            # replay runs harvest ONLY at the recorded EV_SUMMARY
            # boundaries (capture/replay.py) — a teardown harvest here
            # would mint an epoch the original run never had and break
            # the digest-sequence determinism contract
            if not self.ctx.extra.get("replay"):
                self.harvest()
            if self._hist_on:
                # final partial window (no-op when the last harvest
                # already sealed it; what the seal worker holds lands
                # first), then seal the store's active segment so these
                # windows get index rows
                self.seal_window()
                from ..history import HISTORY
                HISTORY.release(self._hist_writer)
                if self._hist_engine is not None:
                    # the release just rotated this run's windows into a
                    # sealed segment: one final pass lets a short-horizon
                    # schedule compact them before the next run
                    try:
                        self._hist_engine.compact_store(
                            self._hist_writer.path)
                    except (OSError, ValueError) as e:
                        _ckpt_log.warning(
                            "teardown compaction failed: %r", e)
            if self._stager is not None:
                # release every in-flight staging block (and zero the
                # occupancy gauge) before the instance goes away
                self._stager.drain()
            if self._lane_stagers:
                # sharded teardown: flush the open round (its batches
                # must land before the final harvest above read them —
                # _merged_locked already did; this is belt) and release
                # every lane's in-flight blocks
                with self._bundle_mu:
                    self._flush_round_locked()
                for st in self._lane_stagers:
                    st.drain()
            if self._sq_engine is not None:
                from ..queries import engine as _queries_engine
                _queries_engine.unregister(self.ctx.run_id)
            self._stats.unregister()
            self._pstats.unregister()
            if self._anomaly_step is not None:
                self._m_anomaly_slots.set(0)
            if self._astats is not None:
                self._astats.unregister()
            if _ckpt_dir is not None:
                # shutdown save stays best-effort, but failures are now
                # logged, counted, and retried — never silently swallowed
                _checkpoint_logged(self)
            with _live_mu:
                _live.pop(self.ctx.run_id, None)

    # checkpoint/resume -----------------------------------------------------

    def _resume(self) -> None:
        """Merge a prior checkpoint into the fresh state (bundle_merge keeps
        absorb semantics; a config change shows up as a treedef/leaf
        mismatch and falls back to fresh)."""
        if _ckpt_dir is None:
            return
        from ..ops.sketches import bundle_merge
        from ..utils.checkpoint import load_pytree
        base = _ckpt_dir / self._ckpt_key
        # broad catch: any unreadable checkpoint (missing, config mismatch,
        # torn zip — np.load raises BadZipFile, not OSError) means fresh
        # state, never a refusal to start — but say so, don't eat it
        try:
            with self._span("tpusketch/resume"):
                prior = load_pytree(base, like=self.bundle)
                with _tm_merge_s.time():
                    self.bundle = bundle_merge(self.bundle, prior)
        except Exception as e:  # noqa: BLE001
            # a checkpoint that EXISTS but fails to load (torn zip,
            # config change, a bundle-treedef change across an upgrade —
            # e.g. the ISSUE-15 overflow/inv fields) resets accumulated
            # state: that must be visible, not a debug whisper; a simply
            # absent file stays quiet
            log_fn = (_ckpt_log.warning
                      if base.with_suffix(".npz").exists()
                      else _ckpt_log.debug)
            log_fn("resume of %s skipped (fresh state): %r",
                   self._ckpt_key, e)
        if self.scorer is not None:
            try:
                self.scorer = load_pytree(
                    Path(str(base) + "-scorer"), like=self.scorer)
            except Exception as e:  # noqa: BLE001
                _ckpt_log.debug("scorer resume of %s skipped: %r",
                                self._ckpt_key, e)
        if self._inv_classes:
            # priority-class state resumes like the bundle: merge the
            # prior class sketches position-wise (a class-config change
            # shows up as a treedef/geometry mismatch and falls back to
            # fresh, loudly when the file exists), so per-class decodes
            # keep reproducing whole-stream totals across a restart
            from ..ops.invertible import inv_merge
            cls_base = Path(str(base) + "-invclasses")
            try:
                prior = load_pytree(
                    cls_base, like=tuple(s for _, s in self._inv_classes))
                self._inv_classes = [
                    (c, inv_merge(s, p))
                    for (c, s), p in zip(self._inv_classes, prior)]
            except Exception as e:  # noqa: BLE001
                log_fn = (_ckpt_log.warning
                          if cls_base.with_suffix(".npz").exists()
                          else _ckpt_log.debug)
                log_fn("class resume of %s skipped (fresh class state): "
                       "%r", self._ckpt_key, e)

    def checkpoint(self) -> None:
        """Host-offload + save current state. Two concurrent runs of the
        same gadget share the key (last writer wins) — merge-on-resume
        still never loses the surviving writer's counts.

        The bundle is snapshotted to HOST arrays under _bundle_mu: the
        run thread's next bundle_update_jit donates (deletes) the buffers
        being read, so an unlocked save from the checkpointer thread hits
        'array has been deleted' mid-write. The slow file write happens
        outside the lock on host copies the device can't invalidate."""
        if _ckpt_dir is None:
            return
        import jax

        from ..utils.checkpoint import save_pytree
        base = _ckpt_dir / self._ckpt_key
        with self._span("tpusketch/checkpoint", key=self._ckpt_key), \
                device_annotation("ig:tpusketch_checkpoint"):
            with self._bundle_mu:
                bundle_host = jax.tree.map(np.asarray, self._merged_locked())
                scorer_host = (jax.tree.map(np.asarray, self.scorer)
                               if self.scorer is not None else None)
                classes_host = (tuple(jax.tree.map(np.asarray, s)
                                      for _, s in self._inv_classes)
                                if self._inv_classes else None)
            save_pytree(base, bundle_host)
            if scorer_host is not None:
                save_pytree(Path(str(base) + "-scorer"), scorer_host)
            if classes_host is not None:
                save_pytree(Path(str(base) + "-invclasses"), classes_host)

    # display helpers -------------------------------------------------------

    def heavy_hitter_rows(self, resolve: Callable[[int], str] | None = None,
                          k: int = 20) -> list[HeavyHitterRow]:
        with self._bundle_mu:
            b = self._merged_locked()
        total = max(float(b.events), 1.0)
        rows = []
        keys = np.asarray(b.topk.keys)
        counts = np.asarray(b.topk.counts)
        order = np.argsort(-counts)[:k]
        for i in order:
            if keys[i] == 0:
                continue
            name = resolve(int(keys[i])) if resolve else f"0x{int(keys[i]):08x}"
            rows.append(HeavyHitterRow(key=name or f"0x{int(keys[i]):08x}",
                                       count=int(counts[i]),
                                       share=float(counts[i]) / total))
        return rows


register(TpuSketch())
