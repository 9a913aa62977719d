"""The full §3.4 chain as ONE black box, from outside the process.

Reference tier: integration/ig/non-k8s drives the `ig` binary against real
containers (pkg/container-utils/testutils/docker.go:114), asserting on its
JSON output. Here the 'container' is an unshared-mount-namespace process
(internal/test/runner.go:103-218's technique), the binary is
`python -m inspektor_gadget_tpu.cli.main`, and the chain exercised is:
procfs discovery → selector match → per-container fanotify attach →
capture → mntns enrichment → JSON rows naming the container.
"""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from inspektor_gadget_tpu.sources.bridge import native_available

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NEEDS = pytest.mark.skipif(
    not native_available() or os.geteuid() != 0
    or not shutil.which("unshare"),
    reason="native capture / root / unshare unavailable")

COMM = "igbb-target"  # distinct comm so the selector matches only ours


@NEEDS
def test_trace_open_containername_black_box(tmp_path):
    # a copied shell gives the fake container a unique comm (the procfs
    # discovery names containers by comm)
    shell = tmp_path / COMM
    shutil.copy("/bin/bash", shell)
    shell.chmod(0o755)
    child = subprocess.Popen(
        ["unshare", "-m", str(shell), "-c",
         "mount -t tmpfs igbb /mnt; "
         "for i in $(seq 1 200); do echo hi > /mnt/igbb_file_$i; "
         "sleep 0.1; done"])
    try:
        time.sleep(1.0)  # container must exist before the CLI's scan
        proc = subprocess.run(
            [sys.executable, "-m", "inspektor_gadget_tpu.cli.main",
             "trace", "open", "--localmanager-containername", COMM,
             "--timeout", "5", "-o", "json"],
            capture_output=True, text=True, cwd=REPO, timeout=240)
    finally:
        child.kill()
        child.wait()
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = []
    for line in proc.stdout.splitlines():
        if line.startswith("{"):
            rows.append(json.loads(line))
    assert rows, proc.stdout[:2000] or proc.stderr[-2000:]
    mine = [r for r in rows if "igbb_file_" in r.get("path", "")]
    assert mine, sorted({r.get("path", "") for r in rows})[:10]
    # enrichment names the container on every row of its mntns
    assert any(r.get("container") == COMM for r in mine), mine[:3]
    # selector scoping: no rows from other mount namespaces leak in
    foreign = [r for r in rows
               if r.get("container") not in ("", COMM, None)]
    assert not foreign, foreign[:5]


@NEEDS
def test_trace_open_wrong_containername_sees_nothing(tmp_path):
    """Negative control (the reference's wrong-mntns test shape,
    tracer_test.go): a selector naming a nonexistent container must
    produce zero rows."""
    shell = tmp_path / COMM
    shutil.copy("/bin/bash", shell)
    shell.chmod(0o755)
    child = subprocess.Popen(
        ["unshare", "-m", str(shell), "-c",
         "mount -t tmpfs igbb /mnt; "
         "for i in $(seq 1 60); do echo hi > /mnt/igbb_neg_$i; "
         "sleep 0.1; done"])
    try:
        time.sleep(1.0)
        proc = subprocess.run(
            [sys.executable, "-m", "inspektor_gadget_tpu.cli.main",
             "trace", "open", "--localmanager-containername", "no-such-ctr",
             "--timeout", "3", "-o", "json"],
            capture_output=True, text=True, cwd=REPO, timeout=240)
    finally:
        child.kill()
        child.wait()
    rows = [json.loads(l) for l in proc.stdout.splitlines()
            if l.startswith("{")]
    assert not [r for r in rows if "igbb_neg_" in r.get("path", "")], rows[:5]
