"""Tier-1 native-build smoke (ISSUE 10 satellite).

When this host carries a C++ toolchain, libigcapture.so must COMPILE
from native/Makefile and one `ig_source_pop_folded` batch must roundtrip
into a pinned staging block. Hosts without a toolchain skip VISIBLY, not
silently: the doctor's `native_toolchain` row reports the same facts the
skip condition reads, so a degraded CI host shows up in `ig-tpu doctor`
instead of as a quietly-green test run.
"""

import os
import shutil
import subprocess
from pathlib import Path

import numpy as np
import pytest

from inspektor_gadget_tpu.doctor import probe_windows

NATIVE = (Path(__file__).resolve().parent.parent
          / "inspektor_gadget_tpu" / "native")

_CXX = os.environ.get("CXX") or "g++"
_HAVE_TOOLCHAIN = bool(shutil.which(_CXX) and shutil.which("make"))

needs_toolchain = pytest.mark.skipif(
    not _HAVE_TOOLCHAIN,
    reason=f"no C++ toolchain ({_CXX}/make) — see doctor native_toolchain row")


def test_doctor_reports_toolchain_row():
    """The skip condition above and the doctor row must agree — that is
    what makes a toolchain-less skip visible instead of silent."""
    w = probe_windows()["native_toolchain"]
    assert w.ok == _HAVE_TOOLCHAIN
    if _HAVE_TOOLCHAIN:
        assert "present" in w.detail
    else:
        assert "missing" in w.detail
        assert "smoke tier skips" in w.detail


@needs_toolchain
def test_makefile_builds_capture_library():
    r = subprocess.run(["make", "-C", str(NATIVE)], capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, (r.stdout + r.stderr)[-2000:]
    assert (NATIVE / "libigcapture.so").exists()


@needs_toolchain
def test_pop_folded_roundtrips_one_batch():
    """One ig_source_pop_folded batch through a pinned pool block: the
    exporter must fill all three SoA lanes, and the folded key universe
    must be exactly the xor-fold of the 64-bit key universe the classic
    pop path reports (tiny vocab → both paths certainly see every key)."""
    import time

    from inspektor_gadget_tpu.sources import PinnedBufferPool
    from inspektor_gadget_tpu.sources.bridge import (
        SRC_SYNTH_EXEC, NativeCapture, native_available,
    )
    assert native_available()
    src = NativeCapture(SRC_SYNTH_EXEC, seed=11, rate=2_000_000, vocab=8,
                        batch_size=4096)
    pool = PinnedBufferPool(4096)
    block = pool.get()
    try:
        with src:
            time.sleep(0.2)
            classic = src.pop()
            assert classic.count > 0
            time.sleep(0.2)
            fb = src.pop_folded(block)
        assert fb.count > 0
        assert fb.capacity == 4096
        assert (fb.weights[:fb.count] == 1).all()
        assert (fb.keys[:fb.count] != 0).all()
        # fold law: the folded lane's key set ⊆ fold64(classic key set)
        # (vocab=8 → every key appears in both multi-thousand-row pops)
        k64 = classic.cols["key_hash"][:classic.count].astype(np.uint64)
        fold = ((k64 >> np.uint64(32))
                ^ (k64 & np.uint64(0xFFFFFFFF))).astype(np.uint32)
        assert set(fb.keys[:fb.count].tolist()) <= set(fold.tolist())
        # mntns lane folds the same way (synthetic ns ids are < 2^32, so
        # the fold is the identity and must land in the classic set)
        m64 = classic.cols["mntns"][:classic.count].astype(np.uint64)
        mfold = ((m64 >> np.uint64(32))
                 ^ (m64 & np.uint64(0xFFFFFFFF))).astype(np.uint32)
        assert set(fb.mntns[:fb.count].tolist()) <= set(mfold.tolist())
    finally:
        src.close()
        pool.put(block)


@needs_toolchain
def test_bridge_lets_make_decide(monkeypatch):
    """The library is not committed and may be older than its sources, so
    the bridge runs a plain `make -C native` (no -B: a no-op when the
    library is current) even when libigcapture.so already exists."""
    from inspektor_gadget_tpu.sources import bridge

    assert (NATIVE / "libigcapture.so").exists()
    calls = []
    real_run = subprocess.run

    def spy(cmd, **kw):
        calls.append(list(cmd))
        return real_run(cmd, **kw)

    monkeypatch.setattr(bridge.subprocess, "run", spy)
    bridge._make_native()
    assert calls == [["make", "-C", str(NATIVE)]]


def test_bridge_build_failure_is_loud(monkeypatch):
    """A failed build is never a quietly-loaded stale library: the reason
    (make's own stderr) reaches whoever asked for the native source."""
    from inspektor_gadget_tpu.sources import bridge

    def failing(cmd, **kw):
        return subprocess.CompletedProcess(cmd, 2, "", "g++: not found")

    monkeypatch.setattr(shutil, "which", lambda name: "/usr/bin/make")
    monkeypatch.setattr(bridge.subprocess, "run", failing)
    monkeypatch.setattr(bridge, "_lib", None)
    monkeypatch.setattr(bridge, "_lib_err", None)
    assert not bridge.native_available()
    with pytest.raises(RuntimeError, match="g\\+\\+: not found"):
        bridge.NativeCapture(bridge.SRC_SYNTH_EXEC)
