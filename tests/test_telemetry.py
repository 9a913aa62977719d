"""Telemetry plane: registry semantics, exposition, pipeline coverage,
and regression tests for the three round-6 bugfixes (checkpoint swallow,
stale formatter specs, TraceStore torn read).

Unit tests use private Registry() instances; end-to-end assertions read
DELTAS of the process-wide default registry (resetting it would orphan the
module-level children instrumented code holds)."""

from __future__ import annotations

import dataclasses
import threading
import time
import urllib.request

import numpy as np
import pytest

import inspektor_gadget_tpu.all_gadgets  # noqa: F401
from inspektor_gadget_tpu import telemetry
from inspektor_gadget_tpu.columns import Columns, TextFormatter, col
from inspektor_gadget_tpu.gadgets import GadgetContext, get
from inspektor_gadget_tpu.params import Collection
from inspektor_gadget_tpu.runtime.local import LocalRuntime
from inspektor_gadget_tpu.telemetry import MetricsServer, Registry


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_counter_semantics():
    r = Registry()
    c = r.counter("req_total", "requests", ("method",))
    c.labels(method="GET").inc()
    c.labels(method="GET").inc(2)
    c.labels(method="PUT").inc(5)
    assert c.labels(method="GET").value == 3
    assert c.labels(method="PUT").value == 5
    with pytest.raises(ValueError):
        c.labels(method="GET").inc(-1)
    with pytest.raises(ValueError):
        c.labels(verb="GET")  # wrong label name


def test_gauge_semantics():
    r = Registry()
    g = r.gauge("depth")
    g.set(4)
    g.inc()
    g.dec(2)
    assert g.value == 3
    g.set_function(lambda: 42)
    assert g.value == 42
    g.set_function(lambda: 1 / 0)  # dead callback reads as 0, not a crash
    assert g.value == 0


def test_histogram_buckets_fixed_log_scale():
    r = Registry()
    h = r.histogram("lat_seconds", buckets=(0.001, 0.01, 0.1))
    for v in (0.0005, 0.005, 0.005, 0.05, 5.0):
        h.observe(v)
    assert h.count == 5
    assert h.sum == pytest.approx(5.0605)
    # cumulative buckets: (le, count<=le)
    assert h.buckets() == [(0.001, 1), (0.01, 3), (0.1, 4),
                           (float("inf"), 5)]
    # a value exactly on a bound counts into that bound's bucket
    h.observe(0.01)
    assert h.buckets()[1] == (0.01, 4)
    with pytest.raises(ValueError):
        r.histogram("bad_seconds", buckets=(0.1, 0.1))


def test_get_or_create_idempotent_and_kind_checked():
    r = Registry()
    a = r.counter("x_total", "first", ("k",))
    b = r.counter("x_total", "second registration ignored", ("k",))
    assert a is b
    with pytest.raises(ValueError):
        r.gauge("x_total")
    with pytest.raises(ValueError):
        r.counter("x_total", labels=("other",))
    h = r.histogram("h_seconds", buckets=(0.1, 1.0))
    assert r.histogram("h_seconds") is h  # None = no opinion on buckets
    assert r.histogram("h_seconds", buckets=(0.1, 1.0)) is h
    with pytest.raises(ValueError):
        r.histogram("h_seconds", buckets=(5.0,))


def test_concurrent_increments_are_exact():
    r = Registry()
    c = r.counter("n_total")
    h = r.histogram("h_seconds", buckets=(1.0,))

    def work():
        for _ in range(5000):
            c.inc()
            h.observe(0.5)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 40000
    assert h.count == 40000
    assert h.buckets() == [(1.0, 40000), (float("inf"), 40000)]


def test_prometheus_text_rendering():
    r = Registry()
    r.counter("ev_total", "events seen", ("gadget",)).labels(
        gadget='trace/exec "x"\nline').inc(7)
    r.gauge("depth").set(2.5)
    r.histogram("lat_seconds", "latency", buckets=(0.01, 1.0)).observe(0.5)
    text = r.render_prometheus()
    assert "# HELP ev_total events seen" in text
    assert "# TYPE ev_total counter" in text
    # label value escaping: backslash, quote, newline
    assert 'ev_total{gadget="trace/exec \\"x\\"\\nline"} 7' in text
    assert "# TYPE depth gauge" in text
    assert "depth 2.5" in text
    assert "# TYPE lat_seconds histogram" in text
    assert 'lat_seconds_bucket{le="0.01"} 0' in text
    assert 'lat_seconds_bucket{le="1.0"} 1' in text
    assert 'lat_seconds_bucket{le="+Inf"} 1' in text
    assert "lat_seconds_sum 0.5" in text
    assert "lat_seconds_count 1" in text


def test_snapshot_deterministic():
    r = Registry()
    # registration order must not leak into the snapshot order
    r.counter("z_total").inc(1)
    r.counter("a_total", labels=("x",)).labels(x="2").inc(2)
    r.counter("a_total", labels=("x",)).labels(x="1").inc(1)
    s1 = r.snapshot()
    s2 = r.snapshot()
    assert s1 == s2
    assert list(s1) == ['a_total{x="1"}', 'a_total{x="2"}', "z_total"]
    import json
    assert json.loads(json.dumps(s1)) == s1  # JSON-embeddable


def test_span_timer_feeds_histogram():
    r = Registry()
    h = r.histogram("span_seconds", buckets=(10.0,))
    with h.time():
        time.sleep(0.01)
    assert h.count == 1
    assert 0.005 < h.sum < 5.0


# ---------------------------------------------------------------------------
# HTTP exposition
# ---------------------------------------------------------------------------

def test_metrics_http_endpoint():
    r = Registry()
    r.counter("served_total").inc(3)
    srv = MetricsServer("127.0.0.1:0", registry=r).start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        body = urllib.request.urlopen(f"{base}/metrics", timeout=5).read()
        assert b"served_total 3" in body
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{base}/nope", timeout=5)
    finally:
        srv.stop()


def test_healthz_endpoint_json():
    """ISSUE 18 satellite: /healthz answers a JSON liveness doc — 200,
    status ok, a monotonic uptime, and a scrape counter that tracks
    /metrics GETs (so a probe can tell 'up but never scraped' from
    'up and scraped')."""
    import json as _json

    srv = MetricsServer("127.0.0.1:0", registry=Registry()).start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        resp = urllib.request.urlopen(f"{base}/healthz", timeout=5)
        assert resp.status == 200
        assert resp.headers["Content-Type"] == "application/json"
        doc = _json.loads(resp.read())
        assert doc["status"] == "ok"
        assert doc["uptime"] >= 0.0
        assert doc["scrapes"] == 0         # nothing scraped yet
        urllib.request.urlopen(f"{base}/metrics", timeout=5).read()
        urllib.request.urlopen(f"{base}/metrics?x=1", timeout=5).read()
        doc2 = _json.loads(urllib.request.urlopen(
            f"{base}/healthz?probe=1", timeout=5).read())
        assert doc2["scrapes"] == 2
        assert doc2["uptime"] >= doc["uptime"]
    finally:
        srv.stop()


def test_parse_addr():
    from inspektor_gadget_tpu.telemetry import parse_addr
    assert parse_addr(":9100") == ("0.0.0.0", 9100)
    assert parse_addr("127.0.0.1:80") == ("127.0.0.1", 80)
    with pytest.raises(ValueError):
        parse_addr("nope")


# ---------------------------------------------------------------------------
# end-to-end: a synthetic gadget run leaves non-zero pipeline counters
# ---------------------------------------------------------------------------

def _sample(snap: dict, key: str) -> float:
    return snap.get(key, 0.0)


def test_gadget_run_populates_pipeline_counters():
    before = telemetry.snapshot()
    desc = get("trace", "exec")
    params = desc.params().to_params()
    params.set("source", "pysynthetic")
    params.set("rate", "200000")
    op_params = Collection()
    from inspektor_gadget_tpu.operators.operators import get as get_op
    sp = get_op("tpusketch").instance_params().to_params()
    sp.set("enable", "true")
    sp.set("log2-width", "8")
    sp.set("hll-p", "6")
    sp.set("entropy-log2-width", "6")
    sp.set("topk", "8")
    sp.set("harvest-interval", "200ms")
    op_params["operator.tpusketch."] = sp
    shown = []
    ctx = GadgetContext(desc, gadget_params=params, operator_params=op_params,
                        timeout=0.6)
    result = LocalRuntime().run_gadget(ctx, on_event=shown.append)
    assert not result.errors()
    assert shown
    after = telemetry.snapshot()

    def delta(key):
        return _sample(after, key) - _sample(before, key)

    g = 'gadget="trace/exec"'
    # source plane
    assert delta(f"ig_source_events_total{{{g}}}") > 0
    assert delta(f"ig_source_batches_total{{{g}}}") > 0
    assert delta(f"ig_display_rows_total{{{g}}}") > 0
    # operator chain
    assert delta(f"ig_gadget_events_total{{{g}}}") > 0
    assert delta('ig_operator_enrich_seconds_count{operator="tpusketch"}') > 0
    # tpusketch device plane
    assert delta(f"ig_tpusketch_events_total{{{g}}}") > 0
    assert delta(f"ig_tpusketch_steps_total{{{g}}}") > 0
    assert delta(f"ig_tpusketch_update_seconds_count{{{g}}}") > 0
    assert delta(f"ig_tpusketch_harvests_total{{{g}}}") > 0


def test_top_metrics_gadget_renders_registry():
    telemetry.counter("ig_test_rows_total").inc(5)
    desc = get("top", "metrics")
    ctx = GadgetContext(desc)
    gadget = desc.new_instance(ctx)
    gadget.setup(ctx)
    telemetry.counter("ig_test_rows_total").inc(7)
    rows = gadget.collect(ctx)
    by_name = {(r.metric, r.labels): r for r in rows}
    row = by_name[("ig_test_rows_total", "")]
    assert row.value == 12
    assert row.kind == "counter"
    assert row.rate > 0  # the 7 incremented since setup()
    # histogram buckets are elided; _count/_sum remain
    assert not any(r.metric.endswith("_bucket") for r in rows)
    # rows render through the ordinary column system
    cols = desc.columns()
    formatter = TextFormatter(cols)
    line = formatter.format_event(row)
    assert "ig_test_rows_total" in line


# ---------------------------------------------------------------------------
# bugfix regressions
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Ev:
    comm: str = col("", width=16)
    pid: int = col(0, width=7, align="right", dtype=np.int32)
    secret: int = col(0, hide=True, dtype=np.int32)


def test_formatter_specs_follow_adjust_widths():
    """Regression: adjust_widths after the first row used to leave stale
    compiled specs — rows kept old widths while the header shrank."""
    cols = Columns(_Ev)
    f = TextFormatter(cols)
    ev = _Ev(comm="a-rather-long-comm", pid=42)
    f.format_event(ev)  # compiles the fast specs at full width
    f.adjust_widths(14)
    fresh = TextFormatter(Columns(_Ev), max_width=14)
    assert f.header() == fresh.header()
    assert f.format_event(ev) == fresh.format_event(ev)


def test_formatter_specs_follow_visibility_changes():
    """Regression: set_visible after the first row used to keep rendering
    the old column set (and KeyError on newly-shown hidden columns)."""
    cols = Columns(_Ev)
    f = TextFormatter(cols)
    ev = _Ev(comm="bash", pid=7, secret=99)
    assert "99" not in f.format_event(ev)
    cols.set_visible(["pid", "secret"])
    row = f.format_event(ev)
    assert "bash" not in row
    assert "99" in row
    assert f.header().split() == ["PID", "SECRET"]


def test_trace_store_readers_never_see_torn_state():
    """Regression: apply() used to mutate the stored resource in place, so
    a concurrent get() could observe the NEW spec with the OLD status."""
    from inspektor_gadget_tpu.gadgets.trace_resource import TraceStore
    store = TraceStore(node_name="n1")
    store.apply({"metadata": {"name": "t1"},
                 "spec": {"gadget": "g/old"}})

    def slow_reconcile(trace):
        time.sleep(0.15)  # window in which readers sample
        trace.status.state = "Reconciled"
        return trace

    store.reconciler.reconcile = slow_reconcile
    t = threading.Thread(target=store.apply, args=(
        {"metadata": {"name": "t1"}, "spec": {"gadget": "g/new"}},))
    t.start()
    torn = []
    while t.is_alive():
        doc = store.get("t1")
        if (doc["spec"]["gadget"] == "g/new"
                and doc["status"]["state"] != "Reconciled"):
            torn.append(doc)
        time.sleep(0.002)
    t.join()
    assert not torn, f"reader saw new spec with stale status: {torn[0]}"
    assert store.get("t1")["status"]["state"] == "Reconciled"


@pytest.fixture()
def sketch_instance(tmp_path):
    from inspektor_gadget_tpu.operators import tpusketch
    from inspektor_gadget_tpu.operators.operators import get as get_op
    tpusketch.set_checkpoint_dir(tmp_path)
    desc = get("trace", "exec")
    ctx = GadgetContext(desc)
    op = get_op("tpusketch")
    p = op.instance_params().to_params()
    p.set("enable", "true")
    p.set("log2-width", "8")
    p.set("hll-p", "6")
    p.set("entropy-log2-width", "6")
    p.set("topk", "8")
    inst = op.instantiate(ctx, None, p)
    yield tmp_path, inst
    from inspektor_gadget_tpu.operators.tpusketch import _live, _live_mu
    with _live_mu:
        _live.pop(ctx.run_id, None)
    tpusketch.set_checkpoint_dir(None)


def test_checkpoint_failure_logged_counted_retried(
        sketch_instance, monkeypatch, caplog):
    """Regression: checkpoint failures used to be `except: pass` — now
    they are logged, bump checkpoint_failures_total, and retry once."""
    import logging

    from inspektor_gadget_tpu.operators import tpusketch
    from inspektor_gadget_tpu.utils import checkpoint as ckpt_mod
    _tmp, inst = sketch_instance
    fail_before = tpusketch._tm_ckpt_fail.value
    ok_before = tpusketch._tm_ckpt_ok.value
    calls = []

    def boom(*a, **kw):
        calls.append(1)
        raise OSError("disk on fire")

    monkeypatch.setattr(ckpt_mod, "save_pytree", boom)
    with caplog.at_level(logging.WARNING, logger="ig-tpu.tpusketch"):
        assert tpusketch.checkpoint_all() == 0
    assert len(calls) == 2  # immediate retry happened
    assert tpusketch._tm_ckpt_fail.value == fail_before + 2
    assert any("checkpoint of trace-exec failed" in r.message
               for r in caplog.records)

    monkeypatch.undo()
    assert tpusketch.checkpoint_all() == 1
    assert tpusketch._tm_ckpt_ok.value == ok_before + 1
    assert (_tmp / "trace-exec.npz").exists()


def test_checkpoint_snapshots_bundle_under_update_pressure(sketch_instance):
    """The checkpointer must survive concurrent enrich_batch updates:
    bundle_update_jit donates its input, so an unlocked reader would hit
    deleted device buffers."""
    from inspektor_gadget_tpu.operators import tpusketch
    from inspektor_gadget_tpu.sources.synthetic import PySyntheticSource
    _tmp, inst = sketch_instance
    src = PySyntheticSource(seed=3, batch_size=512)
    stop = threading.Event()
    errors = []

    def pump():
        try:
            while not stop.is_set():
                inst.enrich_batch(src.generate(512))
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    t = threading.Thread(target=pump)
    t.start()
    try:
        deadline = time.monotonic() + 1.5
        saves = 0
        while time.monotonic() < deadline:
            inst.checkpoint()
            saves += 1
    finally:
        stop.set()
        t.join(timeout=5.0)
    assert not errors
    assert saves > 0
    assert (_tmp / "trace-exec.npz").exists()


# ---------------------------------------------------------------------------
# pinned-pool / H2D staging telemetry + digest donation pin (ISSUE 10)
# ---------------------------------------------------------------------------

def test_ingest_pool_counters_and_inflight_gauge(sketch_instance):
    """The staging plane must account itself: fresh blocks count as pool
    misses, steady-state recycling as hits, and the in-flight H2D gauge
    returns to its baseline once the stager drains — all visible in the
    Prometheus exposition."""
    from inspektor_gadget_tpu.sources import staging
    from inspektor_gadget_tpu.sources.synthetic import PySyntheticSource
    from inspektor_gadget_tpu.telemetry import render_prometheus

    _tmp, inst = sketch_instance
    # the single-chip path stages through lane "0" (ISSUE 14 relabel:
    # the families grew a `lane` label; .total sums across lanes)
    hits0 = staging._tm_pool_hits.total
    miss0 = staging._tm_pool_misses.total
    lane0_hits0 = staging._tm_pool_hits.labels(lane="0").value
    inflight0 = staging._tm_inflight.total

    src = PySyntheticSource(seed=5, batch_size=512)
    for _ in range(8):
        inst.enrich_batch(src.generate(512))
    assert staging._tm_pool_misses.total > miss0, \
        "first staging blocks must be accounted as pool misses"
    assert staging._tm_pool_hits.total > hits0, \
        "steady-state ingest must recycle pinned blocks (pool hits)"
    assert staging._tm_pool_hits.labels(lane="0").value > lane0_hits0, \
        "the unsharded path must stay on lane 0 of the labeled series"
    assert inst._stager is not None
    inst._stager.drain()
    assert staging._tm_inflight.total == inflight0, \
        "drained stager must return the in-flight gauge to baseline"

    text = render_prometheus()
    assert "ig_ingest_pool_hits_total" in text
    assert "ig_ingest_pool_misses_total" in text
    assert "ig_ingest_h2d_inflight" in text


def test_sharded_lane_pool_telemetry_and_gauge_drain():
    """ISSUE 14 satellite: under shard-ingest every device lane accounts
    its OWN pinned pool — lane-labeled miss-then-hit progressions per
    lane, a lane-labeled in-flight gauge that returns to baseline when
    the instance tears down — and the lane label reaches the Prometheus
    exposition."""
    from inspektor_gadget_tpu.operators.operators import get as get_op
    from inspektor_gadget_tpu.sources import staging
    from inspektor_gadget_tpu.sources.synthetic import PySyntheticSource
    from inspektor_gadget_tpu.telemetry import render_prometheus

    desc = get("trace", "exec")
    ctx = GadgetContext(desc)
    op = get_op("tpusketch")
    p = op.instance_params().to_params()
    p.set("enable", "true")
    p.set("log2-width", "8")
    p.set("hll-p", "6")
    p.set("entropy-log2-width", "6")
    p.set("topk", "8")
    p.set("shard-ingest", "true")
    p.set("chips", "2")
    inst = op.instantiate(ctx, None, p)
    assert inst.device_view()["lanes"] == 2

    base = {k: (staging._tm_pool_hits.labels(lane=str(k)).value,
                staging._tm_pool_misses.labels(lane=str(k)).value)
            for k in (0, 1)}
    inflight0 = staging._tm_inflight.total

    src = PySyntheticSource(seed=9, batch_size=512)
    for _ in range(8):
        inst.enrich_batch(src.generate(512))
    for k in (0, 1):
        h0, m0 = base[k]
        assert staging._tm_pool_misses.labels(lane=str(k)).value > m0, \
            f"lane {k}: first blocks must be accounted as misses"
        assert staging._tm_pool_hits.labels(lane=str(k)).value > h0, \
            f"lane {k}: steady state must recycle that lane's blocks"
    inst.harvest()
    inst.post_gadget_run()
    assert staging._tm_inflight.total == inflight0, \
        "teardown must return every lane's in-flight gauge to baseline"

    text = render_prometheus()
    assert 'ig_ingest_pool_hits_total{lane="1"}' in text
    assert 'ig_ingest_h2d_inflight{lane="1"}' in text


def test_ingest_folded_roundtrip_recycles_blocks(sketch_instance):
    """The zero-copy SoA entry point: FoldedBatch lanes from
    folded_block() must absorb into the bundle, recycle through the
    instance's pinned pool (same shape, so put() keeps them), and
    harvest the exact event total."""
    from inspektor_gadget_tpu.sources.batch import FoldedBatch

    _tmp, inst = sketch_instance
    total = 0
    for i in range(4):
        block = inst.folded_block()
        n = 300 + i
        block[0][:n] = np.arange(1, n + 1, dtype=np.uint32)
        block[1][:n] = 1
        block[2][:n] = 101
        inst.ingest_folded(FoldedBatch(lanes=block, count=n))
        total += n
    assert inst._stager is not None
    inst._stager.drain()
    assert inst._pool.free_blocks() > 0, \
        "folded blocks must recycle through the instance pool"
    s = inst.harvest()
    assert s.events == total


def test_harvest_digest_survives_update_pressure(sketch_instance):
    """Donation/aliasing pin (ISSUE 10 satellite, next to the PR-1
    checkpoint-race test above): bundle_digest_jit must never donate its
    input — harvest dispatches it on the LIVE bundle while the
    double-buffered ingest path keeps issuing donating updates, so a
    donating digest would read deleted buffers exactly like the old
    checkpoint race did."""
    from inspektor_gadget_tpu.sources.synthetic import PySyntheticSource

    _tmp, inst = sketch_instance
    src = PySyntheticSource(seed=7, batch_size=512)
    stop = threading.Event()
    errors = []

    def pump():
        try:
            while not stop.is_set():
                inst.enrich_batch(src.generate(512))
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    t = threading.Thread(target=pump)
    t.start()
    try:
        deadline = time.monotonic() + 1.5
        harvests = 0
        while time.monotonic() < deadline:
            s = inst.harvest()
            assert s.events >= 0
            harvests += 1
    finally:
        stop.set()
        t.join(timeout=5.0)
    assert not errors, errors
    assert harvests > 0


# ---------------------------------------------------------------------------
# sketch-history plane telemetry (ISSUE 6 satellite)
# ---------------------------------------------------------------------------

def test_history_counters_and_active_store_gauge(tmp_path):
    """Sealing windows must account into the history plane's OWN
    counters (ig_history_*), visible in the Prometheus exposition, with
    the active-store gauge tracking open writers — and never launder
    through the capture plane's ig_capture_* family."""
    import numpy as np

    from inspektor_gadget_tpu.history import HISTORY, SealedWindow
    from inspektor_gadget_tpu.history.store import HISTORY_METRICS
    from inspektor_gadget_tpu.telemetry import render_prometheus

    windows_before = HISTORY_METRICS.records.labels(type="9").value
    bytes_before = HISTORY_METRICS.bytes.value
    gc_before = HISTORY_METRICS.gc.value
    active_before = HISTORY_METRICS.active.value

    rng = np.random.default_rng(9)

    def win(i):
        # random tables defeat zlib so every frame exceeds the 4 KiB
        # segment floor and rotation/GC fire deterministically
        return SealedWindow(
            gadget="trace/telemetry-probe", node="n0", run_id="r",
            window=i, start_ts=float(i), end_ts=float(i + 1),
            events=10, drops=0,
            cms=rng.integers(0, 2**30, (4, 512)).astype(np.int32),
            hll=np.zeros(16, np.int32),
            ent=np.zeros(8, np.float32),
            topk_keys=np.array([1], np.uint32),
            topk_counts=np.array([5], np.int64), slices={})

    # tight rotation + retention so GC fires deterministically
    w = HISTORY.writer_for("trace/telemetry-probe",
                           base_dir=str(tmp_path),
                           max_segment_bytes=1 << 12, max_segment_age=0,
                           retention_segments=1)
    try:
        for i in range(1, 6):
            HISTORY.append_window(win(i), writer=w)
    finally:
        HISTORY.close_all()

    assert HISTORY_METRICS.records.labels(type="9").value == \
        windows_before + 5
    assert HISTORY_METRICS.bytes.value > bytes_before
    assert HISTORY_METRICS.gc.value > gc_before, \
        "retention GC of sealed history segments was not counted"
    assert HISTORY_METRICS.active.value == active_before  # open+close net 0

    text = render_prometheus()
    assert "ig_history_windows_total" in text
    assert "ig_history_bytes_total" in text
    assert "ig_history_gc_total" in text
    assert "ig_history_active_stores" in text


# ---------------------------------------------------------------------------
# shared-run gauge discipline (ISSUE 12 satellite): attach/detach/evict/
# keepalive-expiry churn must return every per-run gauge EXACTLY to
# baseline — a drifting gauge on a long-lived agent is a lying dashboard
# ---------------------------------------------------------------------------

def _default_metric(name: str, **labels) -> float:
    total = 0.0
    for key, v in telemetry.REGISTRY.snapshot().items():
        if key != name and not key.startswith(name + "{"):
            continue
        if all(f'{k}="{lv}"' in key for k, lv in labels.items()):
            total += v
    return total


def test_shared_run_gauges_return_to_baseline_across_churn():
    """SharedRun-level churn: 3 subscribers attach, one overloads its
    8-deep queue (drops counted per (run, policy, class)) and is evicted
    off its stall window, the rest detach/leave, a late re-attach
    cancels the keepalive, and the final keepalive expiry cancels the
    gadget — after which ig_agent_detached_runs and
    ig_agent_run_subscribers sit exactly where they started."""
    from inspektor_gadget_tpu.agent import wire
    from inspektor_gadget_tpu.agent.service import SharedRun

    detached_before = _default_metric("ig_agent_detached_runs")
    evictions_before = _default_metric(
        "ig_agent_subscriber_evictions_total")

    class _Ctx:
        def __init__(self):
            self.cancelled = threading.Event()

        def cancel(self):
            self.cancelled.set()

    run = SharedRun("gauge-run", "trace/gauge", shared=True,
                    keepalive=0.3, max_subscribers=8, sub_budget=64,
                    node="t")
    ctx = _Ctx()
    run.ctx = ctx
    subs = []
    for i in range(3):
        sub = run.admit({"queue": 8,
                         "priority": "low" if i == 2 else "normal",
                         "evict_after": 0.2 if i == 2 else 60.0})
        assert not isinstance(sub, dict), sub
        q, gen, _ack = run.attach_subscriber(sub, 0)
        subs.append((sub, q, gen))
    assert _default_metric("ig_agent_run_subscribers",
                          run="gauge-run") == 3.0

    # overload: nobody drains, the low-priority 8-deep queue overflows;
    # past its 0.2s stall window the next push evicts it
    for _ in range(20):
        run.push(wire.EV_PAYLOAD_JSON, {"node": "t"}, b"x")
    victim = subs[2][0]
    assert victim.drops > 0
    assert _default_metric("ig_agent_subscriber_drops_total",
                          run="gauge-run", policy="drop-oldest",
                          **{"class": "low"}) >= float(victim.drops)
    time.sleep(0.3)
    run.push(wire.EV_PAYLOAD_JSON, {"node": "t"}, b"x")
    assert victim.evicted and victim.left
    assert _default_metric("ig_agent_subscriber_evictions_total") == \
        evictions_before + 1.0
    assert _default_metric("ig_agent_run_subscribers",
                          run="gauge-run") == 2.0

    # transport-detach one (peers still attached: nothing run-level),
    # then the last leave arms the keepalive
    run.detach(subs[0][0], subs[0][2])
    assert _default_metric("ig_agent_detached_runs") == detached_before
    run.leave(subs[0][0])
    run.leave(subs[1][0])
    assert _default_metric("ig_agent_detached_runs") == \
        detached_before + 1.0
    assert run.keepalive_remaining() > 0.0

    # a re-attach inside the window cancels the countdown and clears the
    # detached gauge; its leave re-arms
    late = run.admit({"queue": 8})
    assert not isinstance(late, dict)
    run.attach_subscriber(late, 0)
    assert _default_metric("ig_agent_detached_runs") == detached_before
    assert not ctx.cancelled.is_set()
    run.leave(late)

    # keepalive expiry cancels the gadget; the run thread would then
    # finish() — after which every gauge is back at baseline
    assert ctx.cancelled.wait(3.0), "keepalive expiry never cancelled"
    run.finish()
    assert _default_metric("ig_agent_detached_runs") == detached_before
    assert _default_metric("ig_agent_run_subscribers",
                          run="gauge-run") == 0.0

    text = telemetry.render_prometheus()
    assert "ig_agent_run_subscribers" in text
    assert "ig_agent_subscriber_drops_total" in text
    assert "ig_agent_subscriber_evictions_total" in text
    assert "ig_agent_attach_refused_total" in text or True  # labeled lazily


def test_agent_active_runs_gauge_baseline_across_shared_lifecycle():
    """Through the real agent: a shared run created, subscribed,
    detached, and keepalive-expired must return ig_agent_active_runs
    and ig_agent_detached_runs exactly to baseline (the run registry
    and the gauges retire together)."""
    import tempfile

    from inspektor_gadget_tpu.agent.client import AgentClient
    from inspektor_gadget_tpu.agent.service import serve

    active_before = _default_metric("ig_agent_active_runs")
    detached_before = _default_metric("ig_agent_detached_runs")
    tmp = tempfile.mkdtemp()
    addr = f"unix://{tmp}/gauge.sock"
    server, agent = serve(addr, node_name="gauge-node")
    try:
        stop = threading.Event()
        holder: dict = {}
        got = threading.Event()

        def owner():
            c = AgentClient(addr, "gauge-node")
            holder["out"] = c.run_gadget(
                "trace", "exec",
                {"gadget.source": "pysynthetic", "gadget.rate": "900"},
                timeout=0.0, run_id="gauge-life", share=True,
                keepalive=0.4,
                on_message=lambda *_: got.set(), stop_event=stop)
            c.close()

        t = threading.Thread(target=owner, daemon=True)
        t.start()
        assert got.wait(30.0), "no stream traffic"
        assert _default_metric("ig_agent_active_runs") == \
            active_before + 1.0
        stop.set()
        t.join(timeout=20.0)
        assert holder["out"]["error"] is None
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            if _default_metric("ig_agent_active_runs") == active_before \
                    and _default_metric("ig_agent_detached_runs") == \
                    detached_before:
                break
            time.sleep(0.1)
        assert _default_metric("ig_agent_active_runs") == active_before
        assert _default_metric("ig_agent_detached_runs") == detached_before
    finally:
        server.stop(grace=0.5)


def test_quantile_plane_counters_follow_value_lane():
    """ISSUE 16 satellite: the DDSketch plane's absorption accounting —
    ig_sketch_quantile_events_total counts every event the value lane
    absorbed, ig_sketch_quantile_zero_total the no-magnitude subset,
    and a plane-OFF instance moves neither."""
    from inspektor_gadget_tpu.operators import tpusketch
    from inspektor_gadget_tpu.operators.operators import get as get_op
    from inspektor_gadget_tpu.sources.batch import EventBatch

    def make(quantiles: str):
        desc = get("trace", "exec")
        ctx = GadgetContext(desc)
        p = get_op("tpusketch").instance_params().to_params()
        p.set("enable", "true")
        p.set("log2-width", "8")
        p.set("hll-p", "6")
        p.set("entropy-log2-width", "6")
        p.set("topk", "8")
        p.set("harvest-interval", "1h")
        p.set("quantiles", quantiles)
        return get_op("tpusketch").instantiate(ctx, None, p)

    def batch(n, zeros):
        b = EventBatch.alloc(n, with_comm=False)
        b.cols["key_hash"][:] = np.arange(1, n + 1, dtype=np.uint64)
        b.cols["aux1"][:] = 1000
        b.cols["aux1"][:zeros] = 0
        b.count = n
        return b

    def counter(name) -> float:
        return sum(v for k, v in telemetry.snapshot().items()
                   if k.startswith(name))

    ev0 = counter("ig_sketch_quantile_events_total")
    z0 = counter("ig_sketch_quantile_zero_total")
    live_before = set(tpusketch._live)
    on, off = make("true"), make("false")
    try:
        on.enrich_batch(batch(64, zeros=5))
        assert counter("ig_sketch_quantile_events_total") == ev0 + 64
        assert counter("ig_sketch_quantile_zero_total") == z0 + 5
        # plane off: the counters must not move — there is no lane
        off.enrich_batch(batch(64, zeros=5))
        assert counter("ig_sketch_quantile_events_total") == ev0 + 64
        assert counter("ig_sketch_quantile_zero_total") == z0 + 5
        # counter discipline: both render in the Prometheus exposition
        text = telemetry.render_prometheus()
        assert "ig_sketch_quantile_events_total" in text
        assert "ig_sketch_quantile_zero_total" in text
    finally:
        with tpusketch._live_mu:
            fresh = [r for r in list(tpusketch._live) if r not in live_before]
            insts = [tpusketch._live.pop(r) for r in fresh]
        for inst in insts:
            if getattr(inst, "_stager", None) is not None:
                inst._stager.drain()
            inst._stats.unregister()
            inst._pstats.unregister()


def test_accuracy_gauges_return_to_baseline_across_churn():
    """Accuracy audit plane (ISSUE 19) gauge discipline: a run that set
    observed-error gauges and the drift ratio must return every
    `ig_sketch_accuracy_*` gauge exactly to baseline on unregister
    (the counter stays monotonic — counters never rewind)."""
    import numpy as np

    from inspektor_gadget_tpu.ops.accuracy import (
        AccuracyStats, ShadowSample, accuracy_block, live_stats)

    obs0 = _default_metric("ig_sketch_accuracy_observed_err")
    ratio0 = _default_metric("ig_sketch_accuracy_ratio")
    fed0 = _default_metric("ig_sketch_audit_samples_total")
    keys = (np.arange(1, 401, dtype=np.uint32) % 40) + 1
    sh = ShadowSample(64)
    sh.update(keys)
    uk, uc = np.unique(keys, return_counts=True)
    a = AccuracyStats("run-acc-tm-1", "trace/exec")
    a.register()
    try:
        a.note_fed(keys.size)
        a.observe_block(accuracy_block(
            events=float(keys.size), depth=3, width=1024, hll_p=8,
            ent_log2_width=6, distinct=float(uk.size) + 1.0,
            entropy_bits=4.0, hh_keys=uk[:8],
            hh_counts=uc[:8].astype(np.int64) + 2, shadow=sh))
        assert _default_metric("ig_sketch_audit_samples_total") == fed0 + 400
        # audited stats set their observed-error gauges + the ratio
        assert _default_metric("ig_sketch_accuracy_observed_err",
                               stat="heavy_hitters") > 0.0
        assert _default_metric("ig_sketch_accuracy_observed_err",
                               stat="distinct") > 0.0
        assert _default_metric("ig_sketch_accuracy_ratio") > 0.0
        assert any(s.run_id == "run-acc-tm-1" for s in live_stats())
        text = telemetry.render_prometheus()
        assert "ig_sketch_accuracy_observed_err" in text
        assert "ig_sketch_accuracy_ratio" in text
        assert "ig_sketch_audit_samples_total" in text
    finally:
        a.unregister()
    # every gauge the run touched is exactly back at baseline
    assert _default_metric("ig_sketch_accuracy_observed_err") == obs0
    assert _default_metric("ig_sketch_accuracy_ratio") == ratio0
    assert not any(s.run_id == "run-acc-tm-1" for s in live_stats())
    # the feed counter is monotonic: unregister must not rewind it
    assert _default_metric("ig_sketch_audit_samples_total") == fed0 + 400


def test_fleet_merge_metrics_lifecycle(monkeypatch):
    """Fleet aggregation tier (ISSUE 20) metric discipline: the depth
    gauge holds the tree's height exactly while a fold is in flight and
    sits back at 0 after (crash paths included — it resets in a
    finally), subtree folds count per aggregator with a result label,
    and the fallback counter trips once per subtree re-folded flat."""
    from inspektor_gadget_tpu.fleet import aggregator as agg_mod
    from inspektor_gadget_tpu.fleet import fold_tree
    from inspektor_gadget_tpu.fleet.sim import GADGET, SimFleet

    assert _default_metric("ig_fleet_merge_depth") == 0.0
    ok0 = _default_metric("ig_fleet_subtree_folds_total", result="ok")
    failed0 = _default_metric("ig_fleet_subtree_folds_total",
                              result="failed")
    fb0 = _default_metric("ig_fleet_fallback_total")

    fleet = SimFleet(8, n_windows=1)
    topo = fleet.topology("auto:4")
    in_flight: list[float] = []

    def spying_fetch(node):
        in_flight.append(_default_metric("ig_fleet_merge_depth"))
        return fleet.fetch_leaf(node)

    tf = fold_tree(topo, spying_fetch, gadget=GADGET)
    assert tf.window is not None
    # set for the WHOLE fold (every leaf pull saw it), 0 again after
    assert in_flight and all(v == float(topo.depth())
                             for v in in_flight)
    assert _default_metric("ig_fleet_merge_depth") == 0.0
    assert _default_metric("ig_fleet_subtree_folds_total",
                           result="ok") == ok0 + len(topo.aggregators())
    assert _default_metric("ig_fleet_subtree_folds_total",
                           result="failed") == failed0
    assert _default_metric("ig_fleet_fallback_total") == fb0

    # client-driven aggregator crash: failed + fallback each tick once,
    # the refold still answers, the gauge still lands back at 0
    real = agg_mod.merged_to_sealed
    crashed: list[str] = []

    def crash_once(merged, *, gadget, node):
        if node == "agg1-000" and not crashed:
            crashed.append(node)
            raise RuntimeError("injected seal crash")
        return real(merged, gadget=gadget, node=node)

    monkeypatch.setattr(agg_mod, "merged_to_sealed", crash_once)
    tf2 = fold_tree(topo, fleet.fetch_leaf, gadget=GADGET)
    monkeypatch.setattr(agg_mod, "merged_to_sealed", real)
    assert tf2.fallback == ["agg1-000"] and tf2.window is not None
    assert _default_metric("ig_fleet_subtree_folds_total",
                           result="failed") == failed0 + 1
    assert _default_metric("ig_fleet_fallback_total") == fb0 + 1
    assert _default_metric("ig_fleet_merge_depth") == 0.0

    # unreachable deployed aggregator: fallback ticks, failed does not
    # (nothing crashed HERE — the remote tier just never answered)
    fetch_subtree = fleet.make_fetch_subtree(fail={"fleet"})
    tf3 = fold_tree(topo, fleet.fetch_leaf,
                    fetch_subtree=fetch_subtree, gadget=GADGET)
    assert tf3.fallback == ["fleet"] and tf3.window is not None
    assert _default_metric("ig_fleet_fallback_total") == fb0 + 2
    assert _default_metric("ig_fleet_subtree_folds_total",
                           result="failed") == failed0 + 1
    assert _default_metric("ig_fleet_merge_depth") == 0.0

    text = telemetry.render_prometheus()
    assert "ig_fleet_merge_depth" in text
    assert "ig_fleet_subtree_folds_total" in text
    assert "ig_fleet_fallback_total" in text
