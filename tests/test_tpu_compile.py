"""Compile the main path's device programs for a DESCRIBED TPU v5e.

No chip is attached here: the TPU compiler is installed and compiles for a
topology that is described, which refuses exactly what the chip's compiler
would refuse (unsupported casts and reductions in a Pallas kernel, a
program that does not fit) at no chip time. A compile that passes is not a
chip run — chip_smoke.py is.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every xdist worker imports
every test file. Everything compiles in this test's own process, and all
of these tests live in this one file (a second file could land on another
worker, whose fixture would skip). The compilation cache is off around the
compiles: an executable for a described device cannot be read back.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from inspektor_gadget_tpu.ops.pallas_kernels import (FUSED_KERNEL_NAME,
                                                     HIST_KERNEL_NAME,
                                                     fused_sketch_planes,
                                                     kernel_in_lowered,
                                                     pallas_histogram)
from inspektor_gadget_tpu.ops.sketches import (bundle_digest, bundle_init,
                                               bundle_ingest_step,
                                               make_bundle_harvest_sharded)
from inspektor_gadget_tpu.operators.tpusketch import STEP_ROWS_FLOOR
from inspektor_gadget_tpu.parallel.mesh import NODE_AXIS

# the operator's default geometry and the smoke's batch
BATCH = 1 << 16
GEOMETRY = dict(depth=4, log2_width=16, hll_p=14, entropy_log2_width=12,
                k=128)
PLANES = dict(depth=4, log2_width=16, ent_log2_width=12, hll_p=14)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any refusal means: no compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()
    # programs traced here while a test steered jax.default_backend() hold
    # the TPU's kernels; a later file in this worker that traced the same
    # shapes would be handed them on the CPU
    jax.clear_caches()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    """The shapes of `tree` placed with `sharding` (a described device
    holds no array, so programs compile from shapes)."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _lane(one_chip, dtype=jnp.uint32):
    return jax.ShapeDtypeStruct((BATCH,), dtype, sharding=one_chip)


def _kernel_in(lowered, name: str) -> bool:
    """The named Pallas kernel is a tpu_custom_call of the lowered program
    (then compile it, which is what can refuse)."""
    lowered.compile()
    return kernel_in_lowered(lowered.as_text(), name)


def test_pallas_histogram_compiles(one_chip):
    hist = jax.jit(lambda k, w: pallas_histogram(k, w, log2_width=12))
    assert _kernel_in(hist.lower(_lane(one_chip),
                                 _lane(one_chip, jnp.float32)),
                      HIST_KERNEL_NAME)


def test_cache_key_survives_a_line_shift_in_the_caller(one_chip, monkeypatch):
    """The persistent cache's key hashes the lowered program, locations
    stripped — but not those INSIDE a Pallas kernel's serialized body.
    With full tracebacks (JAX's default) those name the kernel's callers,
    so two blank lines above a caller change the program; after
    ensure_compile_cache() they do not. as_text() prints no outer
    locations, so it stands for what the key hashes."""
    from inspektor_gadget_tpu.utils.compile_cache import ensure_compile_cache

    src = ("def site(k, w):\n"
           "    return pallas_histogram(k, w, log2_width=12)\n")

    def lowered_from(prefix: str) -> str:
        jax.clear_caches()   # or the second trace reuses the first's kernel
        ns = {"pallas_histogram": pallas_histogram}
        exec(compile(prefix + src, "caller_under_test.py", "exec"), ns)
        return jax.jit(ns["site"]).lower(
            _lane(one_chip), _lane(one_chip, jnp.float32)).as_text()

    was = jax.config.jax_include_full_tracebacks_in_locations
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/nonexistent/unused")
    try:
        jax.config.update("jax_include_full_tracebacks_in_locations", True)
        assert lowered_from("") != lowered_from("\n\n")   # the defect
        ensure_compile_cache()
        assert lowered_from("") == lowered_from("\n\n")
    finally:
        jax.config.update("jax_include_full_tracebacks_in_locations", was)
        jax.clear_caches()


@pytest.mark.parametrize("variant", [
    pytest.param(dict(PLANES, log2_width=12), id="base-2^12"),
    pytest.param(PLANES, id="base-2^16"),
    pytest.param(dict(PLANES, inv_rows=3, inv_log2_buckets=12),
                 id="invertible"),
    pytest.param(dict(PLANES, qt_buckets=2048), id="quantiles"),
    pytest.param(dict(PLANES, inv_rows=3, inv_log2_buckets=12,
                      qt_buckets=2048), id="invertible+quantiles"),
])
def test_fused_sketch_planes_compile(one_chip, variant):
    """Every kernel variant the operator can select on a TPU lowers for
    the v5e (`--tpusketch-invertible`, `--tpusketch-quantiles`, both)."""
    k = _lane(one_chip)
    operands = [k, k, k, _lane(one_chip, jnp.int32)]
    if variant.get("qt_buckets"):
        operands.append(k)
    planes = jax.jit(lambda *a: fused_sketch_planes(*a, **variant))
    assert _kernel_in(planes.lower(*operands), FUSED_KERNEL_NAME)


def test_bundle_digest_compiles(one_chip):
    bundle = _on(one_chip, jax.eval_shape(lambda: bundle_init(**GEOMETRY)))
    jax.jit(bundle_digest).lower(bundle).compile()


def test_seal_window_read_compiles_as_one_program(one_chip):
    """What a seal's capture dispatches on the window planes (the current
    slot and the candidates' estimates for its finish, the ring advanced
    and a fresh HLL for the next window): one program, at the history
    plane's defaults; and the snapshot of the bundle beside it."""
    from inspektor_gadget_tpu.operators.tpusketch import (_seal_snapshot,
                                                          _wcms_window_step)
    from inspektor_gadget_tpu.ops.hll import hll_init
    from inspektor_gadget_tpu.ops.window import wcms_init
    wcms = _on(one_chip, jax.eval_shape(
        lambda: wcms_init(n_slots=8, depth=4, log2_width=12)))
    hll = _on(one_chip, jax.eval_shape(lambda: hll_init(GEOMETRY["hll_p"])))
    cand = jax.ShapeDtypeStruct((GEOMETRY["k"],), jnp.uint32,
                                sharding=one_chip)
    table, counts, ring, fresh = jax.eval_shape(
        _wcms_window_step, wcms, cand, hll)
    assert table.shape == (4, 1 << 12) and counts.shape == (GEOMETRY["k"],)
    assert ring.slots.shape == wcms.slots.shape
    assert fresh.registers.shape == hll.registers.shape
    jax.jit(_wcms_window_step).lower(wcms, cand, hll).compile()
    bundle = _on(one_chip, jax.eval_shape(lambda: bundle_init(**GEOMETRY)))
    jax.jit(_seal_snapshot).lower(bundle).compile()


@pytest.mark.parametrize("slots", [64, 1024])
def test_anomaly_step_compiles_at_the_configurations_size(one_chip, slots):
    """The scorer's one program a harvest (models/autoencoder.py
    `anomaly_step`) at seccomp-node's size and at dense-node's: 64 and
    1,024 rows of 4,096 buckets through 4096-256-64, bf16 products on f32
    parameters, the scorer donated so parameters and Adam's moments are
    updated in place."""
    from inspektor_gadget_tpu.models.autoencoder import (AEConfig, ae_init,
                                                         anomaly_step)
    cfg = AEConfig(input_dim=4096, hidden_dim=256, latent_dim=64)
    scorer = _on(one_chip, jax.eval_shape(lambda: ae_init(cfg)))
    counts = jax.ShapeDtypeStruct((slots, 4096), jnp.float32,
                                  sharding=one_chip)
    mask = jax.ShapeDtypeStruct((slots,), jnp.float32, sharding=one_chip)
    new, scores = jax.eval_shape(anomaly_step, scorer, counts, mask)
    assert scores.shape == (slots,) and scores.dtype == jnp.float32
    compiled = anomaly_step.lower(scorer, counts, mask).compile()
    mem = compiled.memory_analysis()
    # parameters and two moments, 25.5 MB, alias their outputs
    state = 3 * 4 * (2 * (4096 * 256 + 256 * 64) + 256 + 64 + 256 + 4096)
    assert mem.alias_size_in_bytes >= state


def test_sharded_harvest_compiles_with_its_collectives(topo):
    """The collective harvest over a 4-chip (node) mesh: psum/pmax for the
    additive planes and registers, all-gather for the candidate union."""
    mesh = Mesh(np.array(topo.devices[:4]), (NODE_AXIS,))
    like = jax.eval_shape(lambda: bundle_init(**GEOMETRY))
    stacked = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            (4,) + x.shape, x.dtype,
            sharding=NamedSharding(mesh, P(NODE_AXIS))), like)
    text = make_bundle_harvest_sharded(mesh, like).lower(
        stacked).compile().as_text()
    assert "all-reduce" in text and "all-gather" in text
    # what a seal's capture takes of the harvest's output, which is
    # replicated over the mesh (ISSUE 33)
    from inspektor_gadget_tpu.operators.tpusketch import _seal_snapshot
    merged = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, P())), like)
    jax.jit(_seal_snapshot).lower(merged).compile()


@pytest.mark.parametrize("geometry,arm", [
    pytest.param(GEOMETRY, "scatter", id="default-scatter"),
    pytest.param(dict(GEOMETRY, log2_width=12, hll_p=12), "fused",
                 id="narrow-fused"),
])
@pytest.mark.parametrize("rows", [
    pytest.param(BATCH, id="pad"),
    pytest.param(STEP_ROWS_FLOOR, id="ladder-floor"),
])
def test_ingest_step_compiles_with_the_selected_arm(one_chip, monkeypatch,
                                                    geometry, arm, rows):
    """The whole donated ingest step as the operator dispatches it on a
    TPU, at the default geometry (the scatter composition, whose entropy
    plane is the histogram kernel) and at a narrow one where the fused
    kernel is expected to win, at the smoke's batch and at the floor of
    the operator's ladder of step sizes (the sizes between them compile
    too: read once by hand, ISSUE 30). The dispatch asks
    jax.default_backend(), which sees the CPU in this process, so the
    test steers it; the step must hold the arm update_arm names, and the
    program must fit the chip."""
    from inspektor_gadget_tpu.ops.sketches import update_arm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert update_arm(bundle_init(**geometry), rows) == arm
    bundle = _on(one_chip, jax.eval_shape(lambda: bundle_init(**geometry)))
    k = jax.ShapeDtypeStruct((rows,), jnp.uint32, sharding=one_chip)
    drops = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    lowered = jax.jit(bundle_ingest_step, donate_argnums=0).lower(
        bundle, k, k, k, k, drops)
    text = lowered.as_text()
    assert kernel_in_lowered(text, FUSED_KERNEL_NAME) == (arm == "fused")
    assert kernel_in_lowered(text, HIST_KERNEL_NAME) == (arm == "scatter")
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16 << 30
