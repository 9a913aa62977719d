"""Checkpoint/resume wired into the agent (VERDICT r4 item 2).

The reference's closest analogue is pinned BPF maps surviving daemon
restarts (pkg/gadgets/helpers.go:36); here the persisted state is the
tpusketch bundle (+ scorer): periodically host-offloaded by the agent's
checkpointer, merged back on the next start. The kill test is the real
thing — SIGKILL a serving agent mid-ingest, restart it, and assert the
resumed counts include everything the checkpoint had (no silent reset).
"""

import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
from pathlib import Path

import pytest

import inspektor_gadget_tpu.all_gadgets  # noqa: F401
from inspektor_gadget_tpu.gadgets import GadgetContext, get
from inspektor_gadget_tpu.operators import tpusketch
from inspektor_gadget_tpu.operators.operators import get as get_op
from inspektor_gadget_tpu.ops import bundle_init
from inspektor_gadget_tpu.params import Collection
from inspektor_gadget_tpu.runtime.local import LocalRuntime
from inspektor_gadget_tpu.utils.checkpoint import load_pytree

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def ckpt_dir(tmp_path):
    tpusketch.set_checkpoint_dir(tmp_path)
    yield tmp_path
    tpusketch.set_checkpoint_dir(None)


def _run_sketch(timeout=0.8, **extra_params):
    desc = get("trace", "exec")
    params = desc.params().to_params()
    params.set("source", "pysynthetic")
    params.set("rate", "100000")
    summaries = []
    op_params = Collection()
    sketch_params = get_op("tpusketch").instance_params().to_params()
    sketch_params.set("enable", "true")
    sketch_params.set("harvest-interval", "200ms")
    for k, v in extra_params.items():
        sketch_params.set(k, v)
    op_params["operator.tpusketch."] = sketch_params
    ctx = GadgetContext(desc, gadget_params=params, operator_params=op_params,
                        timeout=timeout,
                        extra={"on_sketch_summary": summaries.append})
    result = LocalRuntime().run_gadget(ctx)
    assert not result.errors()
    return summaries


def test_clean_shutdown_saves_and_next_run_resumes(ckpt_dir):
    """post_gadget_run checkpoints; the next run's counts start from it."""
    first = _run_sketch()
    assert first and first[-1].events > 1000
    e1 = first[-1].events
    assert (ckpt_dir / "trace-exec.npz").exists()

    second = _run_sketch()
    # resumed bundle absorbed the first run's events before adding its own
    assert second[-1].events >= e1 + 1000, (second[-1].events, e1)


def test_config_change_falls_back_to_fresh(ckpt_dir):
    _run_sketch()
    # different sketch geometry → treedef/leaf mismatch → fresh state
    small = _run_sketch(**{"log2-width": "10", "hll-p": "10"})
    assert small[-1].events < 1_000_000  # ran fine, no crash on mismatch


def test_corrupt_checkpoint_falls_back_to_fresh(ckpt_dir):
    """A torn .npz (crash mid-write, disk corruption) must mean fresh
    state, never a gadget that refuses to start."""
    (ckpt_dir / "trace-exec.npz").write_bytes(b"not a zip at all")
    (ckpt_dir / "trace-exec.json").write_text("{}")
    summaries = _run_sketch()
    assert summaries and summaries[-1].events > 1000


def test_scorer_checkpoint_roundtrip(ckpt_dir):
    first = _run_sketch(anomaly="true")
    assert first[-1].anomaly
    assert (ckpt_dir / "trace-exec-scorer.npz").exists()
    second = _run_sketch(anomaly="true")
    assert second[-1].anomaly  # scorer resumed and kept scoring


def test_agent_kill_and_resume(tmp_path):
    """SIGKILL a serving agent mid-ingest; restart; merged counts must be
    >= the checkpointed pre-kill counts."""
    ckpt = tmp_path / "ckpt"
    sock_dir = tempfile.mkdtemp()
    addr = f"unix://{sock_dir}/agent.sock"
    env = dict(os.environ)

    def spawn():
        # --platform cpu pins the spawned agent's device plane
        # explicitly: a child of a test never competes for a chip
        return subprocess.Popen(
            [sys.executable, "-m", "inspektor_gadget_tpu.agent.main",
             "serve", "--listen", addr, "--node-name", "ckpt-node",
             "--no-doctor", "--platform", "cpu",
             "--checkpoint-dir", str(ckpt),
             "--checkpoint-interval", "0.3"],
            env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)

    proc = spawn()
    try:
        # wait for the socket to serve
        from inspektor_gadget_tpu.agent.client import AgentClient
        deadline = time.monotonic() + 120
        client = None
        while time.monotonic() < deadline:
            if Path(f"{sock_dir}/agent.sock").exists():
                try:
                    client = AgentClient(addr, "ckpt-node")
                    client.get_catalog(use_cache_on_error=False)
                    break
                except Exception:
                    client = None
            time.sleep(0.5)
        assert client is not None, "agent never came up"

        # unbounded sketch run in the background (ingest is live when killed)
        def run():
            try:
                client.run_gadget(
                    "trace", "exec",
                    {"gadget.source": "pysynthetic", "gadget.rate": "50000",
                     "operator.tpusketch.enable": "true",
                     "operator.tpusketch.harvest-interval": "200ms"},
                    timeout=0.0, outputs=("summary",))
            except Exception:
                pass  # the kill below tears the stream

        t = threading.Thread(target=run, daemon=True)
        t.start()

        # wait for a checkpoint with real counts
        base = ckpt / "trace-exec"
        deadline = time.monotonic() + 60
        pre_kill = 0.0
        while time.monotonic() < deadline:
            try:
                b = load_pytree(base, like=bundle_init())
                pre_kill = float(b.events)
                if pre_kill > 1000:
                    break
            except Exception:
                pass
            time.sleep(0.2)
        assert pre_kill > 1000, "no checkpoint with counts before kill"

        proc.send_signal(signal.SIGKILL)  # mid-ingest, no clean shutdown
        proc.wait(timeout=10)
        t.join(timeout=5)

        # restart: a fresh run must resume (merge), not silently reset
        proc = spawn()
        client2 = None
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            try:
                client2 = AgentClient(addr, "ckpt-node")
                client2.get_catalog(use_cache_on_error=False)
                break
            except Exception:
                client2 = None
                time.sleep(0.5)
        assert client2 is not None, "agent never restarted"

        # no gRPC deadline: the fresh process recompiles the sketch jit
        # (tens of seconds); stop as soon as a summary proves the resume
        summaries = []
        stop = threading.Event()

        def on_s(node, s):
            summaries.append(s)
            if s["events"] >= pre_kill:
                stop.set()

        watchdog = threading.Timer(120.0, stop.set)
        watchdog.start()
        res = client2.run_gadget(
            "trace", "exec",
            {"gadget.source": "pysynthetic", "gadget.rate": "50000",
             "operator.tpusketch.enable": "true",
             "operator.tpusketch.harvest-interval": "200ms"},
            timeout=0.0, outputs=("summary",), on_summary=on_s,
            stop_event=stop)
        watchdog.cancel()
        assert res["error"] is None, res["error"]
        assert summaries, "no summaries after restart"
        assert max(s["events"] for s in summaries) >= pre_kill, (
            f"reset detected: {summaries[-1]['events']} < {pre_kill}")
        client2.close()
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)
