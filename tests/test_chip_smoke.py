"""The bring-up contract, as far as a CPU host can hold it: chip_smoke.py's
tiny rehearsal passes and says so in a parseable last line, a failed phase
or a missing TPU is a non-zero exit with NO result line, the compile cache
can be placed from outside, and local agents get an explicit platform."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _smoke(*args, cwd=REPO, script=SMOKE):
    # the suite's eight virtual CPU devices are conftest's business, not
    # the smoke's: it reports the devices JAX gives it
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    return subprocess.run([sys.executable, script, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=600)


def _result_lines(stdout: str) -> list[dict]:
    out = []
    for line in stdout.splitlines():
        try:
            doc = json.loads(line)
        except ValueError:
            continue
        if isinstance(doc, dict) and "ok" in doc:
            out.append(doc)
    return out


def test_cpu_rehearsal_passes_and_last_line_parses():
    r = _smoke("--platform", "cpu", "--seed", "7")
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    phases = {}
    for line in lines[:-1]:
        phases[json.loads(line)["phase"]] = json.loads(line)
    assert list(phases) == ["acquire", "step-times", "local-runtime",
                            "invertible", "quantiles", "narrow", "anomaly",
                            "anomaly-dense", "agent", "done"]
    # truthful about what ran: no kernel on the CPU, and it says so, at
    # the run's geometry and at the one where a TPU takes the kernel
    for times in (phases["step-times"], phases["step-times"]["narrow"]):
        assert times["served_path"] == "scatter"
        assert times["fused_equals_scatter"] is True
        assert "fused(interpret)" in times
    local = phases["local-runtime"]
    assert local["path"] == "scatter" and local["state_on"] == ["cpu"]
    # the operator's arm counter names the arm of every step it ran
    for name in ("local-runtime", "invertible", "quantiles", "narrow"):
        assert phases[name]["arm_steps"] == {"scatter": phases[name]["steps"]}
    assert local["windows_sealed"] >= 5 and local["harvests"] >= 6
    assert local["events_absorbed"] == local["events_offered"] - local["drops"]
    assert local["generator"] == "native C++ synthetic"
    assert phases["invertible"]["decoded_equals_exact"] is True
    # the scorer met the plain replay, and each planted fault did not
    anomaly = phases["anomaly"]
    assert anomaly["histograms_exact"] and anomaly["profile_exact"]
    assert anomaly["score_gap"] <= anomaly["tolerance"]
    assert all(gap > anomaly["tolerance"]
               for gap in anomaly["fault_gaps"].values())
    assert (anomaly["containers"], anomaly["slots"]) == (64, 64)
    # the same on a node of 1,024 containers, the scorer primed at the
    # slots that hold them and every window's slices exact
    dense = phases["anomaly-dense"]
    assert dense["histograms_exact"] and dense["profile_exact"]
    assert dense["score_gap"] <= dense["tolerance"]
    assert all(gap > dense["tolerance"]
               for gap in dense["fault_gaps"].values())
    assert (dense["containers"], dense["slots"], dense["primed_slots"]) == (
        1024, 1024, 1024)
    assert dense["slices_exact"] and dense["slices_dropped"] == 0
    assert dense["windows"] >= 2
    agent = phases["agent"]
    assert agent["checkpoints"] >= 2 and agent["checkpoint_failures"] == 0
    assert agent["generator"] == "native C++ synthetic"
    assert agent["events_absorbed"] == agent["events_offered"] - agent["drops"]
    # the checkpointer met a busy ingest loop, and the smoke says how busy
    assert agent["steps_per_checkpoint"] >= 1


def test_cpu_rehearsal_of_the_four_chip_path():
    """`--chips 4` runs the sharded path and its comparison and no other
    phase; on the CPU four virtual devices stand in."""
    r = _smoke("--platform", "cpu", "--chips", "4")
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [json.loads(x) for x in r.stdout.strip().splitlines()]
    assert [x.get("phase") for x in lines[:-1]] == ["acquire", "sharded",
                                                    "done"]
    assert lines[-1]["device"]["count"] == 4
    sharded = lines[1]
    assert len(set(sharded["lane_devices"])) == 4
    assert sharded["harvest_collectives"] == ["all-gather", "all-reduce"]


def test_tpu_absent_fails_without_a_result():
    """What the driver runs (`python chip_smoke.py`) where JAX finds no
    accelerator: non-zero, says why, prints no result."""
    r = _smoke()
    assert r.returncode != 0
    assert "tpu requested" in r.stderr
    assert _result_lines(r.stdout) == []


def test_outside_the_repo_fails_without_a_result(tmp_path):
    """chip_smoke.py alone in a directory proves nothing and must say so."""
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    r = _smoke("--platform", "cpu", cwd=str(tmp_path),
               script=str(tmp_path / "chip_smoke.py"))
    assert r.returncode != 0
    assert _result_lines(r.stdout) == []


def test_failed_phase_raises_and_prints_no_result(monkeypatch, capsys):
    """A phase whose check fails is an uncaught SmokeFailure (a non-zero
    exit), never a printed note above an ok line: here the reference
    misses a batch, so the device-vs-host event accounting cannot hold."""
    import chip_smoke

    real = chip_smoke.ExactStream.observe
    seen = []

    def lossy(self, batch):
        seen.append(batch.count)
        if len(seen) != 2:
            real(self, batch)

    monkeypatch.setattr(chip_smoke.ExactStream, "observe", lossy)
    with pytest.raises(chip_smoke.SmokeFailure, match="device absorbed"):
        chip_smoke.main(["--platform", "cpu"])
    assert _result_lines(capsys.readouterr().out) == []


@pytest.mark.parametrize("host,device,match", [
    ((1000, 5), (1000, 5), None),
    ((1000, 5), (999, 5), "device absorbed"),
    ((1000, 5), (1000, 4), "device counted"),
    # past the float32 counters' exact range the smoke refuses to judge:
    # no tolerance that a lost event could hide in
    (((1 << 24) - 5, 5), ((1 << 24) - 5, 5), "past 2\\^24"),
])
def test_counters_are_checked_exactly(host, device, match):
    import chip_smoke

    exact = chip_smoke.ExactStream()
    exact.events, exact.drops = host
    summary = {"events": device[0], "drops": device[1]}
    if match is None:
        chip_smoke.check_counters(summary, exact)
    else:
        with pytest.raises(chip_smoke.SmokeFailure, match=match):
            chip_smoke.check_counters(summary, exact)


# -- the compile cache -------------------------------------------------------

def test_compile_cache_leaves_an_outside_directory_alone(monkeypatch,
                                                         tmp_path):
    import jax

    from inspektor_gadget_tpu.utils import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.ensure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing set


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(monkeypatch):
    import jax

    from inspektor_gadget_tpu.utils import compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = compile_cache.ensure_compile_cache()
        second = compile_cache.ensure_compile_cache()
        assert first == second == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


# -- one process per chip ----------------------------------------------------

@pytest.mark.parametrize("nodes,pinned,want", [
    ((), "", ["cpu", "cpu", "cpu"]),               # no TPU on the host
    (("accel0",), "", ["tpu", "cpu", "cpu"]),      # one agent owns the chip
    (("vfio/0", "vfio/1", "vfio/2", "vfio/3"), "",
     ["tpu", "cpu", "cpu"]),                       # one process drives all four
    (("vfio/vfio",), "", ["cpu", "cpu", "cpu"]),   # the bare vfio control node
    (("accel0",), "cpu", ["cpu", "cpu", "cpu"]),   # JAX pinned elsewhere
])
def test_deploy_local_platforms_are_explicit(monkeypatch, tmp_path, nodes,
                                             pinned, want):
    from inspektor_gadget_tpu.cli import deploy
    for node in nodes:
        (tmp_path / node).parent.mkdir(exist_ok=True)
        (tmp_path / node).touch()
    monkeypatch.setattr(deploy, "TPU_DEVICE_GLOBS", tuple(
        g.replace("/dev", str(tmp_path), 1) for g in deploy.TPU_DEVICE_GLOBS))
    monkeypatch.setenv("JAX_PLATFORMS", pinned)
    got = deploy.local_agent_platforms(3)
    assert got == want
    for i, platform in enumerate(got):
        argv = deploy.local_agent_argv(f"node-{i}", f"127.0.0.1:{5000 + i}",
                                       platform)
        assert argv[argv.index("--platform") + 1] == platform
    # the DaemonSet stays on the agent's default (auto): a node without a
    # TPU serves on the CPU instead of crash-looping
    assert "--platform" not in deploy.render_manifests()


def test_deploy_local_logs_go_to_a_private_directory(monkeypatch, tmp_path):
    """Agent logs: a fresh 0700 directory under the temp dir, recorded in
    the state file — never a fixed name in a shared /tmp that a symlink
    could be planted at."""
    import subprocess as sp
    import tempfile

    from inspektor_gadget_tpu.cli import deploy
    started = []

    class FakePopen:
        pid = 2 ** 22 + 1   # above pid_max's default: never alive

        def __init__(self, argv, stdout, stderr):
            started.append(argv)
            os.write(stdout, b"hello\n")

    monkeypatch.setattr(sp, "Popen", FakePopen)
    monkeypatch.setattr(deploy, "STATE_FILE", str(tmp_path / "state.json"))
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    targets = deploy.deploy_local(2)
    log_dir = deploy.local_log_dir()
    assert os.path.dirname(log_dir) == str(tmp_path)
    assert os.stat(log_dir).st_mode & 0o777 == 0o700
    assert sorted(os.listdir(log_dir)) == ["node-0.log", "node-1.log"]
    assert open(os.path.join(log_dir, "node-1.log")).read() == "hello\n"
    assert list(targets) == ["node-0", "node-1"] and len(started) == 2
    assert set(deploy.local_platforms().values()) <= {"cpu", "tpu"}
