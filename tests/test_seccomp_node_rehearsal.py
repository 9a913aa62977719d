"""The benchmark's `seccomp-node.saturate` cell rehearsed on the CPU
(ISSUE 31): `chipbench/run.py --workload seccomp-node.saturate --platform
cpu` as a process of its own, at the sizes of `chipbench/rehearsal.json`
(an autoencoder 256-256-64 over the source's 64 containers). The sound run
comes out `correct` with the five metrics that read what the anomaly path
adds, and meets `chipbench/reference_scorer.py`; the same run with one of
the scorer's training steps skipped does not. The harness's own `correct`
cannot see the scores yet (its tap records neither `mntns` nor `aux2`), so
the comparison is made here, on a tap that records them beside it. Every
name ends in `.cpu_rehearsal`; none is a device number.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "chipbench"
CELL = "seccomp-node.saturate"
ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}

# run.py's main() as it is, with three things stood in for. A `--trace 1`
# run reads the per-layer metrics, and a CPU trace has no device plane to
# reduce, so the reduction is stood in for (as tests/
# test_shard_host_rehearsal.py does), here with a made-up time for the
# scorer's program so that the two readers of the trace have something to
# read; the device's kind, because `peaks.json` rightly knows no CPU; and
# the tap, which also hands every batch and summary to the reference's
# recorder. `fault` names what chip_smoke.py's `scorer_fault` plants
# around the run, if anything
REHEARSAL = """
import json, sys
sys.path[:0] = [{bench!r}, {root!r}]
import jax, numpy as np
import run as harness
import chip_smoke, reference_scorer as ref
harness.trace_reduction.load = lambda path: []
harness.trace_reduction.reduce_trace = lambda planes, spans, anchor: {{
    "window_s": 1.0, "busy_s": 0.0, "busiest_busy_s": 0.0, "chips": 1,
    "programs": {{"jit_anomaly_step": [0.004, 16]}}, "collective_s": 0.0,
    "harvests": 0, "device_ops": [], "idle_gaps": []}}
real_run = harness.Run
harness.Run = lambda tap, config, _kind, setup_s: real_run(
    tap, config, "TPU v5 lite", setup_s)
rec = ref.Recorder()

class RecordingTap(harness.Tap):
    def on_batch(self, batch):
        rec.on_batch(batch)
        super().on_batch(batch)

    def on_summary(self, summary):
        rec.on_summary(summary)
        super().on_summary(summary)

harness.Tap = RecordingTap
with chip_smoke.scorer_fault({fault!r}):
    rc = harness.main(sys.argv[1:])
from inspektor_gadget_tpu.models.autoencoder import AEConfig, ae_init
start = jax.tree.map(np.asarray, ae_init(AEConfig(
    input_dim=256, hidden_dim=256, latent_dim=64)).params)
print(json.dumps({{"scorer": ref.compare(rec, start, 256),
                   "tolerance": ref.TOLERANCE}}), flush=True)
sys.exit(rc)
"""


def rehearse(fault: str, trace: int, seed: int) -> tuple[dict, dict]:
    out = subprocess.run(
        [sys.executable, "-c",
         REHEARSAL.format(bench=str(BENCH), root=str(ROOT), fault=fault),
         "--workload", CELL, "--seed", str(seed), "--seconds", "3",
         "--trace", str(trace), "--platform", "cpu"],
        cwd=ROOT, env=ENV, capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    line, scorer = out.stdout.strip().splitlines()[-2:]
    return json.loads(line), json.loads(scorer)


def test_the_sound_rehearsal_is_correct_and_reads_what_the_scorer_adds():
    line, scorer = rehearse("", 1, 3100000041)
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    values = {k: v["value"] for k, v in line["metrics"].items()}
    assert all(k.endswith(".cpu_rehearsal") for k in values)
    get = lambda name: values[name + ".cpu_rehearsal"]   # noqa: E731
    assert get("gadget_record_host_ms_per_batch") > 0.0
    assert get("container_dists_host_ms_per_batch") > 0.0
    assert get("anomaly_host_ms") > 0.0
    assert get("anomaly_device_ms") == 0.25          # the stood-in time
    assert 0.0 < get("anomaly_step_roofline") < 100.0
    assert get("window_compiles") == 0.0
    # what no stage covers is a fixed 0.2-0.3 ms a turn: held by the
    # millisecond, as tests/test_shard_host_rehearsal.py holds it
    share = get("turn_accounted_share")
    assert share <= 100.0
    assert get("turn_host_ms_per_batch") * (100.0 - share) / 100.0 < 1.0
    # the recorder left source_filter and the distributions tpusketch_post:
    # what is left of the chain is small beside them
    assert get("chain_other_host_ms_per_batch") > 0.0
    # and the scores met the plain replay on every summary
    r = scorer["scorer"]
    assert r["score_keys_equal"] and r["harvests"] >= 10
    assert r["scores_compared"] >= 64 * 10
    assert r["score_gap"] <= scorer["tolerance"], r


def test_the_rehearsal_with_a_training_step_skipped_fails_the_reference():
    line, scorer = rehearse("skipped", 0, 3100000043)
    # the harness's own comparison cannot see it
    assert line["correct"] is True
    r = scorer["scorer"]
    assert r["score_keys_equal"]
    assert r["score_gap"] > scorer["tolerance"], r
