"""Distributed tracing plane: span semantics, cross-process propagation
through a real client→agent gRPC run, Chrome-trace export, ring
retention, the flight recorder (including crash dumps), the bounded
device acquisition, and the logger satellites
(StreamLogger run/trace IDs, get_logger level stability)."""

from __future__ import annotations

import json
import logging
import tempfile
import threading
import time

import pytest

import inspektor_gadget_tpu.all_gadgets  # noqa: F401
from inspektor_gadget_tpu.agent.client import AgentClient
from inspektor_gadget_tpu.agent.service import serve
from inspektor_gadget_tpu.gadgets import GadgetContext, get
from inspektor_gadget_tpu.params import Collection
from inspektor_gadget_tpu.runtime.grpc_runtime import GrpcRuntime
from inspektor_gadget_tpu.telemetry.tracing import (
    RECORDER,
    TRACER,
    FlightRecorder,
    SpanContext,
    Tracer,
    export_chrome,
    install_crash_handlers,
    parse_traceparent,
)


# ---------------------------------------------------------------------------
# span + context semantics (private Tracer instances)
# ---------------------------------------------------------------------------

def test_traceparent_roundtrip_and_malformed():
    ctx = SpanContext(trace_id="ab" * 16, span_id="cd" * 8, sampled=True)
    back = parse_traceparent(ctx.to_traceparent())
    assert back == ctx
    off = SpanContext(trace_id="ab" * 16, span_id="cd" * 8, sampled=False)
    assert parse_traceparent(off.to_traceparent()).sampled is False
    for bad in ("", "00-zz-xx-01", "nope", "00-abc-def-01", 42, None):
        assert parse_traceparent(bad) is None


def test_span_parent_linkage_and_contextvar_nesting():
    t = Tracer(capacity=64)
    with t.span("outer") as outer:
        with t.span("inner"):  # implicit parent via contextvar
            pass
        assert t.current_context() == outer.context
    assert t.current_context() is None
    recs = {r.name: r for r in t.records()}
    assert recs["inner"].trace_id == recs["outer"].trace_id
    assert recs["inner"].parent_id == recs["outer"].span_id
    assert recs["outer"].parent_id == ""
    assert recs["inner"].duration >= 0


def test_span_records_error_and_explicit_parent():
    t = Tracer(capacity=64)
    remote = SpanContext(trace_id="11" * 16, span_id="22" * 8)
    with pytest.raises(RuntimeError):
        with t.span("child", parent=remote):
            raise RuntimeError("boom")
    (rec,) = t.records()
    assert rec.trace_id == remote.trace_id
    assert rec.parent_id == remote.span_id
    assert "RuntimeError: boom" in rec.error


def test_ring_eviction_is_bounded():
    from inspektor_gadget_tpu.telemetry.tracing import _tm_evicted
    before = _tm_evicted.value
    t = Tracer(capacity=10)
    for i in range(35):
        with t.span(f"s{i}"):
            pass
    recs = t.records()
    assert len(recs) == 10
    assert [r.name for r in recs] == [f"s{i}" for i in range(25, 35)]
    assert _tm_evicted.value - before == 25


def test_head_sampling_propagates_but_records_nothing():
    t = Tracer(capacity=64, sample_rate=0.0)
    with t.span("root") as root:
        assert root.context.sampled is False
        with t.span("child") as child:
            # the trace identity still propagates for downstream peers
            assert child.context.trace_id == root.context.trace_id
    assert t.records() == []


# ---------------------------------------------------------------------------
# end-to-end: one trace across client → agent RPC → operators → device plane
# ---------------------------------------------------------------------------

def _sketch_run_ctx(timeout: float) -> GadgetContext:
    desc = get("trace", "exec")
    params = desc.params().to_params()
    params.set("source", "pysynthetic")
    params.set("rate", "20000")
    params.set("batch-size", "256")
    from inspektor_gadget_tpu.operators.operators import get as get_op
    sp = get_op("tpusketch").instance_params().to_params()
    for k, v in (("enable", "true"), ("log2-width", "8"), ("hll-p", "6"),
                 ("entropy-log2-width", "6"), ("topk", "8"),
                 ("harvest-interval", "300ms")):
        sp.set(k, v)
    op_params = Collection()
    op_params["operator.tpusketch."] = sp
    return GadgetContext(desc, gadget_params=params,
                         operator_params=op_params, timeout=timeout)


@pytest.fixture(scope="module")
def agent_node():
    tmp = tempfile.mkdtemp()
    addr = f"unix://{tmp}/agent.sock"
    server, agent = serve(addr, node_name="trace-node")
    # warm the sketch-plane jit for these shapes: under full-suite load a
    # first-touch compile can eat a short run's whole window
    from inspektor_gadget_tpu.runtime.local import LocalRuntime
    LocalRuntime().run_gadget(_sketch_run_ctx(1.0))
    yield {"trace-node": addr}
    server.stop(grace=0.5)


def _run_traced(agents) -> str:
    """Run trace/exec with the sketch plane through the gRPC fan-out;
    returns the minted trace ID. Retries once: under heavy suite load a
    short run can deliver zero events without that being a bug."""
    for attempt in (1, 2):
        ctx = _sketch_run_ctx(timeout=1.2 * attempt)
        runtime = GrpcRuntime(dict(agents))
        events = []
        result = runtime.run_gadget(ctx, on_event=events.append)
        runtime.close()
        assert not result.errors()
        if events:
            return ctx.extra["trace_ctx"].trace_id
    raise AssertionError("no events delivered in two attempts")


def test_one_trace_id_with_correct_parentage_across_grpc_run(agent_node):
    tid = _run_traced(agent_node)
    # the agent's run span closes as its stream generator unwinds, which
    # can lag the client return by a beat
    deadline = time.monotonic() + 5.0
    needed = {"client/run/trace/exec", "client/node/trace-node",
              "agent/RunGadget", "agent/run/trace/exec", "run/trace/exec",
              "op/tpusketch", "tpusketch/h2d", "tpusketch/update",
              "tpusketch/harvest"}
    while time.monotonic() < deadline:
        names = {r.name for r in TRACER.records(trace_id=tid)}
        if needed <= names:
            break
        time.sleep(0.05)
    recs = TRACER.records(trace_id=tid)
    names = {r.name for r in recs}
    assert needed <= names, f"missing {needed - names}"

    # correct parentage: a device-plane span must chain up to the client
    # root through operator chain, agent run, agent RPC, and node spans
    by_id = {r.span_id: r for r in recs}
    update = next(r for r in recs if r.name == "tpusketch/update")
    chain = [update.name]
    r = update
    while r.parent_id:
        r = by_id[r.parent_id]
        chain.append(r.name)
    assert chain == ["tpusketch/update", "op/tpusketch", "run/trace/exec",
                     "agent/run/trace/exec", "agent/RunGadget",
                     "client/node/trace-node", "client/run/trace/exec"]


def test_chrome_trace_export_schema(agent_node):
    tid = _run_traced(agent_node)
    time.sleep(0.3)
    doc = export_chrome(TRACER.records(), trace_id=tid)
    # JSON-serializable and Perfetto-shaped
    parsed = json.loads(json.dumps(doc))
    assert parsed["displayTimeUnit"] == "ms"
    events = parsed["traceEvents"]
    spans = [e for e in events if e["ph"] == "X"]
    meta = [e for e in events if e["ph"] == "M"]
    assert spans and meta
    for e in spans:
        assert {"name", "ph", "cat", "ts", "dur", "pid", "tid",
                "args"} <= set(e)
        assert isinstance(e["ts"], (int, float)) and e["dur"] >= 0
        assert e["args"]["trace_id"] == tid
    # every span's parent_id is resolvable inside the same export
    ids = {e["args"]["span_id"] for e in spans}
    for e in spans:
        assert e["args"]["parent_id"] == "" or e["args"]["parent_id"] in ids
    # metadata names processes for the merged view
    assert any(m["name"] == "process_name" for m in meta)


def test_chrome_trace_contains_pipeline_stage_spans(agent_node):
    """Pipeline health plane (ISSUE 18): each harvest tick renders one
    span per instrumented stage, with the watermark/quantile accounting
    in the span args and the run's trace ID threaded through — so
    `debug trace export` shows a real pipeline timeline."""
    tid = _run_traced(agent_node)
    time.sleep(0.3)
    recs = TRACER.records(trace_id=tid)
    stage = [r for r in recs if r.name.startswith("tpusketch/stage/")]
    names = {r.name for r in stage}
    assert {"tpusketch/stage/pop", "tpusketch/stage/h2d"} <= names, names
    pop = next(r for r in stage if r.name == "tpusketch/stage/pop")
    assert {"watermark_s", "p50_s", "p99_s", "count"} <= set(pop.attrs)
    assert pop.attrs["count"] > 0
    # ring warmup guarantees starved ticks, so the stager span rendered
    stager = next(r for r in stage if r.name == "tpusketch/stage/stager")
    assert stager.attrs["starved"] > 0
    assert 0.0 < stager.attrs["starved_ratio"] <= 1.0
    # stage spans parent under the harvest span of the same trace
    by_id = {r.span_id: r for r in recs}
    assert by_id[pop.parent_id].name == "tpusketch/harvest"
    # and they survive the Chrome export with identity + accounting args
    doc = export_chrome(recs, trace_id=tid)
    spans = [e for e in doc["traceEvents"]
             if e["ph"] == "X" and e["name"].startswith("tpusketch/stage/")]
    assert spans
    for e in spans:
        assert e["args"]["trace_id"] == tid
    pe = next(e for e in spans if e["name"] == "tpusketch/stage/pop")
    assert pe["args"]["count"] > 0 and "watermark_s" in pe["args"]


def test_flight_record_over_dump_state_rpc(agent_node):
    _run_traced(agent_node)
    client = AgentClient(next(iter(agent_node.values())), "trace-node")
    fr = client.flight_record()
    client.close()
    assert fr["pid"] > 0
    assert fr["facts"].get("node")
    assert any(s["name"].startswith("agent/") for s in fr["spans"])
    # the snapshot round-trips through the wire as JSON already
    assert isinstance(fr["logs"], list) and isinstance(fr["errors"], list)


def test_remote_log_lines_carry_run_and_trace_ids(agent_node):
    """A server-side ctx.logger warning must reach the client stream with
    the run/trace IDs threaded through the StreamLogger header."""
    got = []
    client = AgentClient(next(iter(agent_node.values())), "trace-node")
    parent = SpanContext(trace_id="ef" * 16, span_id="12" * 8)
    # a no-target traceloop run fails loudly inside the gadget run; the
    # server's ctx.logger.exception record multiplexes onto the stream
    res = client.run_gadget(
        "traceloop", "traceloop", {}, timeout=2.0,
        on_log=lambda node, sev, msg, hdr: got.append((sev, msg, hdr)),
        trace_ctx=parent,
    )
    client.close()
    assert res["error"] and "target" in res["error"]
    assert got, "no log records multiplexed onto the stream"
    sev, msg, hdr = got[0]
    assert "gadget run failed" in msg
    assert hdr.get("run_id")
    assert hdr.get("trace_id") == parent.trace_id


# ---------------------------------------------------------------------------
# flight recorder: crash dumps
# ---------------------------------------------------------------------------

def test_flight_record_dump_on_simulated_thread_crash(tmp_path):
    rec = FlightRecorder(Tracer(capacity=16))
    rec.set_fact("platform", "cpu")
    with rec.tracer.span("doomed-work"):
        pass
    rec.record_log({"ts": time.time(), "level": "INFO", "logger": "t",
                    "msg": "about to die", "run_id": "", "trace_id": ""})
    path = tmp_path / "flight.json"
    prev = threading.excepthook
    threading.excepthook = lambda args: None  # silence the default printer
    try:
        uninstall = install_crash_handlers(str(path), recorder=rec,
                                           signals=())
        t = threading.Thread(target=lambda: 1 / 0)
        t.start()
        t.join()
        uninstall()
    finally:
        threading.excepthook = prev
    dumped = json.loads(path.read_text())
    assert dumped["facts"]["platform"] == "cpu"
    assert any(s["name"] == "doomed-work" for s in dumped["spans"])
    assert any(l["msg"] == "about to die" for l in dumped["logs"])
    assert any(e["kind"] == "ZeroDivisionError" for e in dumped["errors"])
    assert "1 / 0" in dumped["errors"][-1]["traceback"] or \
        dumped["errors"][-1]["traceback"]


def test_flight_record_dump_on_sigterm(tmp_path):
    """A killed process leaves evidence: SIGTERM → dump, then exit via
    the chained handler. Exercised in a subprocess so the signal's
    process-exit semantics stay real."""
    import subprocess
    import sys
    path = tmp_path / "flight-term.json"
    code = f"""
import os, signal
from inspektor_gadget_tpu.telemetry.tracing import (
    RECORDER, TRACER, install_crash_handlers)
with TRACER.span("pre-kill"):
    pass
RECORDER.set_fact("platform", "cpu")
install_crash_handlers({str(path)!r})
os.kill(os.getpid(), signal.SIGTERM)
"""
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0  # terminated, not a clean exit
    dumped = json.loads(path.read_text())
    assert any(s["name"] == "pre-kill" for s in dumped["spans"])
    assert any(e["kind"] == "signal" for e in dumped["errors"])


def test_ig_logger_records_land_in_flight_recorder():
    """telemetry/tracing attaches a handler to the 'ig-tpu' root logger:
    any component's warning is retained for post-mortem reads."""
    marker = f"flight-marker-{time.time_ns()}"
    logging.getLogger("ig-tpu.test-component").warning(marker)
    snap = RECORDER.snapshot()
    assert any(l["msg"] == marker for l in snap["logs"])


# ---------------------------------------------------------------------------
# device acquisition: in this process, loud, no fallback
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("requested", ["cpu", "auto"])
def test_acquire_platform_reports_what_jax_found(requested):
    """cpu pins the CPU backend; auto is whatever JAX itself reports
    (here: no accelerator). Both record the outcome for doctor and the
    flight recorder."""
    import jax

    from inspektor_gadget_tpu.utils import platform_probe as pp
    out = pp.acquire_platform(requested)
    assert out["requested"] == requested
    assert out["platform"] == jax.devices()[0].platform == "cpu"
    assert out["device_count"] == len(jax.devices())
    assert out["device_kind"] == jax.devices()[0].device_kind
    assert pp.last_acquire() == out
    assert RECORDER.snapshot()["facts"]["platform"] == "cpu"


def test_acquire_platform_tpu_absent_raises():
    """Asking for the TPU where JAX reports none is an error that says
    why — never a CPU run under another name."""
    from inspektor_gadget_tpu.utils import platform_probe as pp
    with pytest.raises(pp.PlatformUnavailable,
                       match="tpu requested but JAX reports 'cpu'"):
        pp.acquire_platform("tpu")
    with pytest.raises(ValueError):
        pp.acquire_platform("gpu")


def test_agent_serve_tpu_absent_exits_nonzero(tmp_path, capsys):
    """`agent.main serve --platform tpu` on a host without one exits
    non-zero before it binds anything, and says why."""
    from inspektor_gadget_tpu.agent.main import main as agent_main
    rc = agent_main(["serve", "--platform", "tpu", "--no-doctor",
                     "--listen", f"unix://{tmp_path}/a.sock"])
    assert rc == 1
    assert "tpu requested" in capsys.readouterr().err
    assert not (tmp_path / "a.sock").exists()


def test_agent_serve_exposes_platform_flag():
    """The agent's arg surface carries --platform auto|tpu|cpu."""
    from inspektor_gadget_tpu.agent.main import main as agent_main
    with pytest.raises(SystemExit) as e:
        agent_main(["serve", "--platform", "gpu"])
    assert e.value.code == 2  # argparse rejects unknown platforms


# ---------------------------------------------------------------------------
# logger satellites
# ---------------------------------------------------------------------------

def test_get_logger_does_not_clobber_configured_level():
    from inspektor_gadget_tpu.utils.logger import DEBUG, get_logger
    name = f"ig-tpu.level-test-{time.time_ns()}"
    first = get_logger(name, DEBUG)
    assert first.level == logging.DEBUG
    # a later caller with the default level must NOT win
    again = get_logger(name)
    assert again is first
    assert again.level == logging.DEBUG


def test_stream_logger_threads_run_and_trace_ids():
    from inspektor_gadget_tpu.utils.logger import WARN, StreamLogger
    pushed = []
    sl = StreamLogger(lambda kind, hdr, payload: pushed.append(
        (kind, hdr, payload)), run_id="r-1", trace_id="t-1")
    sl.warn("careful")
    (kind, hdr, payload) = pushed[0]
    assert kind == WARN << 16
    assert hdr == {"run_id": "r-1", "trace_id": "t-1"}
    assert payload == b"careful"


def test_stream_log_handler_maps_levels():
    from inspektor_gadget_tpu.utils.logger import (
        ERROR, INFO, StreamLogger, StreamLogHandler)
    pushed = []
    handler = StreamLogHandler(StreamLogger(
        lambda kind, hdr, payload: pushed.append((kind >> 16, payload))))
    log = logging.getLogger(f"ig-tpu.slh-{time.time_ns()}")
    log.addHandler(handler)
    log.setLevel(logging.INFO)
    log.info("hello %d", 7)
    log.error("bad")
    log.removeHandler(handler)
    assert (INFO, b"hello 7") in pushed
    assert (ERROR, b"bad") in pushed
