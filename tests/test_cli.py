"""Black-box CLI tests (model: the reference's ig integration tier —
integration/ig/* runs the built binary and matches output)."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CLI = [sys.executable, "-m", "inspektor_gadget_tpu.cli.main"]


def run_cli(*args, timeout=120):
    return subprocess.run(CLI + list(args), capture_output=True, text=True,
                          timeout=timeout)


def test_cli_list_and_catalog():
    r = run_cli("list")
    assert r.returncode == 0
    assert "trace" in r.stdout and "exec" in r.stdout
    assert len(r.stdout.strip().splitlines()) >= 25

    r = run_cli("catalog")
    cat = json.loads(r.stdout)
    assert len(cat["gadgets"]) >= 25
    assert any(op["name"] == "tpusketch" for op in cat["operators"])


def test_cli_trace_exec_json_output():
    r = run_cli("trace", "exec", "--source", "pysynthetic", "--rate", "3000",
                "--timeout", "1", "-o", "json")
    assert r.returncode == 0, r.stderr
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    assert len(lines) > 10
    row = json.loads(lines[0])
    assert row["comm"].startswith("proc-") and row["pid"] > 0


def test_cli_bad_param_exits_2():
    r = run_cli("trace", "exec", "--source", "bogus", "--timeout", "1")
    assert r.returncode == 2
    assert "not in" in r.stderr


def test_cli_deploy_render():
    r = run_cli("deploy", "--render")
    assert r.returncode == 0
    assert "kind: DaemonSet" in r.stdout
    assert "google.com/tpu" in r.stdout


def test_cli_traces_lifecycle_against_live_daemon(tmp_path):
    """The kubectl-gadget advise ergonomics (§3.5) as a black box: a real
    agent daemon subprocess + `ig-tpu traces` verbs from separate CLI
    processes (ref: cmd/kubectl-gadget/utils/trace.go:340-848)."""
    import os
    import time

    addr = f"unix://{tmp_path}/agent.sock"
    remote = f"n0={addr}"
    daemon = subprocess.Popen(
        [sys.executable, "-m", "inspektor_gadget_tpu.agent.main", "serve",
         "--listen", addr, "--node-name", "n0", "--no-doctor",
         "--platform", "cpu"],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        cwd=REPO)
    try:
        deadline = time.time() + 120
        up = False
        while time.time() < deadline and not up:
            if os.path.exists(f"{tmp_path}/agent.sock"):
                r = run_cli("traces", "list", "--remote", remote)
                up = r.returncode == 0
            if not up:
                time.sleep(1.0)
        assert up, "agent never served"

        r = run_cli("traces", "start", "--remote", remote, "--name", "bb1",
                    "--gadget", "advise/seccomp-profile",
                    "-p", "source=pysynthetic", "-p", "rate=20000")
        assert r.returncode == 0, r.stderr
        assert "bb1 Started" in r.stdout
        time.sleep(1.0)
        r = run_cli("traces", "generate", "--remote", remote,
                    "--name", "bb1")
        assert r.returncode == 0, r.stderr
        assert "defaultAction" in r.stdout
        r = run_cli("traces", "delete", "--remote", remote, "--name", "bb1")
        assert r.returncode == 0 and "deleted=True" in r.stdout
    finally:
        daemon.terminate()
        try:
            daemon.wait(timeout=15)
        except subprocess.TimeoutExpired:
            daemon.kill()
