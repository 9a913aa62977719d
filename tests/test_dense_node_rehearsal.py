"""The benchmark's `dense-node.saturate` cell rehearsed on the CPU (ISSUE
34): `chipbench/run.py --workload dense-node.saturate --platform cpu` as a
process of its own, at the sizes of `chipbench/rehearsal.json` (an
autoencoder 256-256-64; the configuration's 1,024 containers and
`history-max-slices 4096` stay as they are). The run comes out `correct`
with every metric this cell adds to the line, the scorer primed at 1,024
slots, and meets `chipbench/reference_scorer.py`. The harness's own
`correct` cannot see the scores or the slices yet (its tap records no
`mntns`), so that comparison is made here, on a tap that records them
beside it. Every name ends in `.cpu_rehearsal`; none is a device number.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "chipbench"
CELL = "dense-node.saturate"
ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}

# run.py's main() as it is, with four things stood in for, as
# tests/test_seccomp_node_rehearsal.py does: the trace's reduction (a CPU
# trace has no device plane), here with a made-up time for the scorer's
# program so that the two readers of the trace have something to read;
# the device's kind, because `peaks.json` rightly knows no CPU; the tap,
# which also hands every batch and summary to the reference's recorder;
# and the profiler's directory
REHEARSAL = """
import json, sys, tempfile
from pathlib import Path
sys.path[:0] = [{bench!r}, {root!r}]
import jax, numpy as np
import run as harness
import reference_scorer as ref
# a directory of its own for the profiler: the harness empties the one it
# shares with every other traced rehearsal this suite runs beside this one
harness.TRACE_DIR = Path(tempfile.mkdtemp(prefix="dense-rehearsal-trace-"))
harness.trace_reduction.load = lambda path: []
harness.trace_reduction.reduce_trace = lambda planes, spans, anchor: {{
    "window_s": 1.0, "busy_s": 0.0, "busiest_busy_s": 0.0, "chips": 1,
    "programs": {{"jit_anomaly_step": [0.004, 16]}}, "collective_s": 0.0,
    "harvests": 0, "device_ops": [], "idle_gaps": []}}
real_run = harness.Run
harness.Run = lambda tap, config, _kind, setup_s: real_run(
    tap, config, "TPU v5 lite", setup_s)
rec = ref.Recorder()
blocks = []

class RecordingTap(harness.Tap):
    def on_batch(self, batch):
        rec.on_batch(batch)
        super().on_batch(batch)

    def on_summary(self, summary):
        rec.on_summary(summary)
        blocks.append(summary.pipeline)
        super().on_summary(summary)

harness.Tap = RecordingTap
rc = harness.main(sys.argv[1:])
from inspektor_gadget_tpu.models.autoencoder import AEConfig, ae_init
start = jax.tree.map(np.asarray, ae_init(AEConfig(
    input_dim=256, hidden_dim=256, latent_dim=64)).params)
print(json.dumps({{"scorer": ref.compare(rec, start, 256),
                   "tolerance": ref.TOLERANCE,
                   "anomaly": blocks[-1]["anomaly"],
                   "slices": blocks[-1]["slices"]}}), flush=True)
sys.exit(rc)
"""

DENSE_METRICS = (
    "gadget_record_host_ms_per_batch.dense",
    "container_dists_host_ms_per_batch.dense",
    "anomaly_host_ms.dense", "anomaly_device_ms.dense",
    "anomaly_step_roofline.dense", "seal_finish_ms.dense",
    "slices_dropped_share.dense")


def test_the_dense_rehearsal_is_correct_and_reads_every_new_metric():
    out = subprocess.run(
        [sys.executable, "-c",
         REHEARSAL.format(bench=str(BENCH), root=str(ROOT)),
         "--workload", CELL, "--seed", "3400000041", "--seconds", "4",
         "--trace", "1", "--platform", "cpu"],
        cwd=ROOT, env=ENV, capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    line, beside = map(json.loads, out.stdout.strip().splitlines()[-2:])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    values = {k: v["value"] for k, v in line["metrics"].items()}
    assert all(k.endswith(".cpu_rehearsal") for k in values)
    get = lambda name: values[name + ".cpu_rehearsal"]   # noqa: E731
    for name in DENSE_METRICS:
        assert name + ".cpu_rehearsal" in values, name
    # seccomp-node's five are its own: this cell reads the copies only
    assert "anomaly_host_ms.cpu_rehearsal" not in values
    assert get("gadget_record_host_ms_per_batch.dense") > 0.0
    assert get("container_dists_host_ms_per_batch.dense") > 0.0
    assert get("anomaly_host_ms.dense") > 0.0
    assert get("anomaly_device_ms.dense") == 0.25       # the stood-in time
    assert 0.0 < get("anomaly_step_roofline.dense") < 100.0
    assert get("seal_finish_ms.dense") > 0.0
    assert get("slices_dropped_share.dense") == 0.0
    assert get("window_compiles") == 0.0
    assert get("ring_drop_share") >= 0.0
    # 1,024 containers, a slice each and a cell each and the kind's, the
    # scorer primed at the slots that hold them
    assert beside["anomaly"]["containers"] == 1024
    assert beside["anomaly"]["slots"] == 1024
    assert beside["anomaly"]["primed_slots"] == 1024
    assert beside["slices"]["dropped"] == 0
    assert beside["slices"]["admitted"] == 2 * beside["slices"]["cells"] + 1
    assert beside["slices"]["cells"] > 500    # a busy machine seals less
    # and the scores met the plain replay on every summary
    r = beside["scorer"]
    assert r["score_keys_equal"] and r["harvests"] >= 10
    assert r["scores_compared"] >= 500 * 10
    assert r["score_gap"] <= beside["tolerance"], r
