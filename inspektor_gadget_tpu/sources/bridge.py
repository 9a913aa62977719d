"""ctypes bridge to libigcapture.so — the cgo analogue.

Loads the native capture library (`make` decides whether it must be
built or rebuilt first, see _make_native) and exposes sources
that pop struct-of-arrays EventBatches with zero per-event Python work:
numpy buffers are handed to C++ which fills them directly.

Reference contract being replaced: cilium/ebpf perf.Reader → Go structs
(pkg/gadgets/*/tracer/tracer.go run loops). Loss/seq accounting carried
through (tracer.go:148-151's LostSamples handling).
"""

from __future__ import annotations

import ctypes
import subprocess
import time
from pathlib import Path

import numpy as np

from .batch import EventBatch, FoldedBatch

SRC_SYNTH_EXEC = 1
SRC_SYNTH_TCP = 2
SRC_SYNTH_DNS = 3
SRC_PROC_EXEC = 100
SRC_PROC_TCP = 101
SRC_FANOTIFY_EXEC = 102
SRC_FANOTIFY_OPEN = 103
SRC_MOUNTINFO = 104
SRC_SOCK_DIAG = 105
SRC_KMSG_OOM = 106
SRC_PTRACE = 108
SRC_FANOTIFY_RUNC = 109
SRC_PERF_CPU = 110
SRC_BLK_TRACE = 111
SRC_TCP_BYTES = 112
SRC_AUDIT = 113
SRC_CAP_TRACE = 114
SRC_FS_TRACE = 115
SRC_SOCK_STATE = 116
SRC_SIG_TRACE = 117
SRC_PKT_DNS = 200
SRC_PKT_SNI = 201
SRC_PKT_FLOW = 202

# kinds that take a "key=value\x1f..." config string (create_cfg path)
_CFG_KINDS = {SRC_FANOTIFY_OPEN, SRC_MOUNTINFO, SRC_SOCK_DIAG, SRC_KMSG_OOM,
              SRC_PTRACE, SRC_FANOTIFY_RUNC, SRC_PERF_CPU, SRC_BLK_TRACE,
              SRC_TCP_BYTES, SRC_AUDIT, SRC_CAP_TRACE, SRC_FS_TRACE,
              SRC_SOCK_STATE, SRC_SIG_TRACE}


def make_cfg(**kw) -> str:
    """Build the config string for cfg-kind sources. A cmd list is joined
    with \\x1e (unit separators keep arbitrary argv content safe)."""
    parts = []
    for k, v in kw.items():
        if v is None:
            continue
        if isinstance(v, (list, tuple)):
            v = "\x1e".join(str(x) for x in v)
        parts.append(f"{k}={v}")
    return "\x1f".join(parts)

_NATIVE_DIR = Path(__file__).resolve().parent.parent / "native"
_LIB_PATH = _NATIVE_DIR / "libigcapture.so"

_lib = None
_lib_err: str | None = None


def _load():
    global _lib, _lib_err
    if _lib is not None or _lib_err is not None:
        return _lib
    try:
        _lib = _load_and_bind()
    except (OSError, AttributeError) as e:
        # recorded, not swallowed: native_available() answers False and
        # every path that ASKED for the native source raises with this
        _lib_err = str(e)
    return _lib


def _make_native() -> None:
    """Let make decide whether libigcapture.so is current: a plain
    `make -C native` is a no-op when the library is newer than its
    sources and rebuilds a stale one — the library is not committed, so
    what git would check out always builds here. A lock serialises the
    builders (test workers, agents started together). Only a host with
    no `make` at all loads an existing library unchecked."""
    import fcntl
    import os
    import shutil
    if shutil.which("make") is None:
        if _LIB_PATH.exists():
            return
        raise OSError(f"{_LIB_PATH.name} is not built and this host has "
                      "no `make` to build it")
    lock = os.open(_NATIVE_DIR, os.O_RDONLY)  # flock on the directory
    try:
        fcntl.flock(lock, fcntl.LOCK_EX)
        r = subprocess.run(["make", "-C", str(_NATIVE_DIR)],
                           capture_output=True, text=True)
    finally:
        os.close(lock)
    if r.returncode != 0:
        raise OSError(f"make -C {_NATIVE_DIR} failed (rc={r.returncode}): "
                      + (r.stderr or r.stdout).strip()[-400:])


def _load_and_bind():
    _make_native()
    lib = ctypes.CDLL(str(_LIB_PATH))

    u64, u32, i64, f64 = (ctypes.c_uint64, ctypes.c_uint32, ctypes.c_int64,
                          ctypes.c_double)
    p64 = ctypes.POINTER(ctypes.c_uint64)
    p32 = ctypes.POINTER(ctypes.c_uint32)
    lib.ig_source_create.argtypes = [u32, u64, f64, u32, f64, u32, u32]
    lib.ig_source_create.restype = u64
    lib.ig_source_create_cfg.argtypes = [u32, ctypes.c_char_p, u32]
    lib.ig_source_create_cfg.restype = u64
    lib.ig_source_set_filter.argtypes = [u64, p64, i64]
    lib.ig_source_set_filter.restype = ctypes.c_int
    lib.ig_source_filtered.argtypes = [u64]
    lib.ig_source_filtered.restype = u64
    lib.ig_ptrace_exit_status.argtypes = [u64]
    lib.ig_ptrace_exit_status.restype = ctypes.c_int
    lib.ig_perf_supported.argtypes = []
    lib.ig_perf_supported.restype = ctypes.c_int
    lib.ig_blktrace_supported.argtypes = []
    lib.ig_blktrace_supported.restype = ctypes.c_int
    lib.ig_tcpinfo_supported.argtypes = []
    lib.ig_tcpinfo_supported.restype = ctypes.c_int
    lib.ig_audit_supported.argtypes = []
    lib.ig_audit_supported.restype = ctypes.c_int
    lib.ig_captrace_supported.argtypes = []
    lib.ig_captrace_supported.restype = ctypes.c_int
    lib.ig_fstrace_supported.argtypes = []
    lib.ig_fstrace_supported.restype = ctypes.c_int
    lib.ig_sockstate_supported.argtypes = []
    lib.ig_sockstate_supported.restype = ctypes.c_int
    lib.ig_sigtrace_supported.argtypes = []
    lib.ig_sigtrace_supported.restype = ctypes.c_int
    for fn in ("ig_source_start", "ig_source_stop", "ig_source_destroy"):
        getattr(lib, fn).argtypes = [u64]
        getattr(lib, fn).restype = ctypes.c_int
    lib.ig_source_pop_batch.argtypes = [u64, i64] + [p64] * 5 + [p32] * 4 + [
        ctypes.c_char_p]
    lib.ig_source_pop_batch.restype = i64
    lib.ig_source_pop_folded.argtypes = [u64, i64, p32, p32, p32]
    lib.ig_source_pop_folded.restype = i64
    lib.ig_source_pop_folded2.argtypes = [u64, i64, p32, p32, p32, p32]
    lib.ig_source_pop_folded2.restype = i64
    lib.ig_source_drops.argtypes = [u64]
    lib.ig_source_drops.restype = u64
    lib.ig_source_produced.argtypes = [u64]
    lib.ig_source_produced.restype = u64
    lib.ig_synth_generate.argtypes = [u64, i64, p64, p64, p32, p32]
    lib.ig_synth_generate.restype = i64
    lib.ig_synth_generate_folded.argtypes = [u64, i64, p32]
    lib.ig_synth_generate_folded.restype = i64
    lib.ig_vocab_lookup.argtypes = [u64, u64, ctypes.c_char_p, i64]
    lib.ig_vocab_lookup.restype = i64
    lib.ig_vocab_lookup_batch.argtypes = [
        u64, p64, i64, ctypes.c_char_p, i64,
        ctypes.POINTER(ctypes.c_int32)]
    lib.ig_vocab_lookup_batch.restype = i64
    lib.ig_sources_stats.argtypes = [p64, p32] + [p64] * 7 + [i64]
    lib.ig_sources_stats.restype = i64
    lib.ig_fanotify_supported.argtypes = []
    lib.ig_fanotify_supported.restype = ctypes.c_int
    lib.ig_containers_set.argtypes = [u64, ctypes.c_char_p, i64]
    lib.ig_containers_remove.argtypes = [u64]
    lib.ig_containers_lookup.argtypes = [u64, ctypes.c_char_p, i64]
    lib.ig_containers_lookup.restype = i64
    lib.ig_containers_count.restype = i64
    return lib


# -- containers map (ref: pkg/gadgettracermanager/containers-map) -----------

def containers_map_set(mntns: int, name: str) -> None:
    lib = _load()
    if lib is not None:
        raw = name.encode("utf-8", "replace")
        lib.ig_containers_set(mntns, raw, len(raw))


def containers_map_remove(mntns: int) -> None:
    lib = _load()
    if lib is not None:
        lib.ig_containers_remove(mntns)


def containers_map_lookup(mntns: int) -> str:
    lib = _load()
    if lib is None:
        return ""
    buf = ctypes.create_string_buffer(256)
    n = lib.ig_containers_lookup(mntns, buf, 256)
    return buf.raw[:n].decode("utf-8", "replace") if n > 0 else ""


def native_available() -> bool:
    return _load() is not None


def blktrace_supported() -> bool:
    """Per-IO block window (tracefs block events) available on this host."""
    lib = _load()
    return lib is not None and bool(lib.ig_blktrace_supported())


def tcpinfo_supported() -> bool:
    """Per-connection TCP byte counters (sock_diag INET_DIAG_INFO)."""
    lib = _load()
    return lib is not None and bool(lib.ig_tcpinfo_supported())


def fanotify_supported() -> bool:
    """fanotify mount marks available (needs CAP_SYS_ADMIN)."""
    lib = _load()
    return lib is not None and bool(lib.ig_fanotify_supported())


def audit_supported() -> bool:
    """Host-wide kernel audit window (NETLINK_AUDIT readlog multicast)."""
    lib = _load()
    return lib is not None and bool(lib.ig_audit_supported())


def captrace_supported() -> bool:
    """cap_capable tracepoint window (tracefs, kernel >= 6.7)."""
    lib = _load()
    return lib is not None and bool(lib.ig_captrace_supported())


def fstrace_supported() -> bool:
    """raw_syscalls tracepoint window (host-wide fsslower)."""
    lib = _load()
    return lib is not None and bool(lib.ig_fstrace_supported())


def sockstate_supported() -> bool:
    """inet_sock_set_state tracepoint (event-driven trace/tcp)."""
    lib = _load()
    return lib is not None and bool(lib.ig_sockstate_supported())


def sigtrace_supported() -> bool:
    """signal_generate tracepoint (full sigsnoop parity)."""
    lib = _load()
    return lib is not None and bool(lib.ig_sigtrace_supported())


_SRC_KIND_NAMES = {
    SRC_SYNTH_EXEC: "synth/exec", SRC_SYNTH_TCP: "synth/tcp",
    SRC_SYNTH_DNS: "synth/dns", SRC_PROC_EXEC: "netlink/proc",
    SRC_PROC_TCP: "proc/tcp", SRC_FANOTIFY_EXEC: "fanotify/exec",
    SRC_FANOTIFY_OPEN: "fanotify/open", SRC_MOUNTINFO: "mountinfo",
    SRC_SOCK_DIAG: "sock_diag", SRC_KMSG_OOM: "kmsg/oom",
    SRC_PTRACE: "ptrace", SRC_FANOTIFY_RUNC: "fanotify/runc",
    SRC_PERF_CPU: "perf/cpu", SRC_BLK_TRACE: "blk/trace",
    SRC_TCP_BYTES: "sock_diag/tcpinfo", SRC_AUDIT: "netlink/audit",
    SRC_CAP_TRACE: "tracefs/cap", SRC_FS_TRACE: "tracefs/fs",
    SRC_SOCK_STATE: "tracefs/sock", SRC_SIG_TRACE: "tracefs/signal",
    SRC_PKT_DNS: "pkt/dns",
    SRC_PKT_SNI: "pkt/sni", SRC_PKT_FLOW: "pkt/flow",
}


def sources_stats(cap: int = 256) -> list[dict]:
    """Enumerate every live native capture source with self-stats (the
    top/ebpf contract: reference pkg/gadgets/top/ebpf/tracer.go:55-418 —
    per-program runtime + counters; here per-source capture-thread CPU
    time, ring occupancy/capacity, produced/consumed/drops/filtered)."""
    lib = _load()
    if lib is None:
        return []
    ids = np.zeros(cap, np.uint64)
    kinds = np.zeros(cap, np.uint32)
    cols = [np.zeros(cap, np.uint64) for _ in range(7)]
    n = lib.ig_sources_stats(
        _p64(ids), _p32(kinds), *[_p64(c) for c in cols], cap)
    if n <= 0:
        return []
    produced, consumed, drops, filtered, ring_len, ring_cap, cpu_ns = cols
    out = []
    for i in range(int(n)):
        k = int(kinds[i])
        out.append({
            "id": int(ids[i]),
            "kind": k,
            "kind_name": _SRC_KIND_NAMES.get(k, str(k)),
            "produced": int(produced[i]),
            "consumed": int(consumed[i]),
            "drops": int(drops[i]),
            "filtered": int(filtered[i]),
            "ring_len": int(ring_len[i]),
            "ring_cap": int(ring_cap[i]),
            "cpu_ns": int(cpu_ns[i]),
        })
    return out


def _p64(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64))


def _p32(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32))


class NativeCapture:
    """A native capture source popping columnar EventBatches."""

    def __init__(self, kind: int, *, seed: int = 0, rate: float = 0.0,
                 vocab: int = 1000, zipf_s: float = 1.2, ring_pow2: int = 20,
                 batch_size: int = 8192, cfg: str = "",
                 containers: int = 64):
        lib = _load()
        if lib is None:
            raise RuntimeError(f"native capture unavailable: {_lib_err}")
        self._lib = lib
        if kind in _CFG_KINDS:
            self._h = lib.ig_source_create_cfg(
                kind, cfg.encode("utf-8", "replace"), ring_pow2)
        else:
            self._h = lib.ig_source_create(kind, seed, rate, vocab, zipf_s,
                                           ring_pow2, containers)
        if self._h == 0:
            raise ValueError(f"unknown source kind {kind}")
        self.batch_size = batch_size
        self._batch = EventBatch.alloc(batch_size)
        self._seq = 0
        self.kind = kind
        # pipeline-health watermark: wall clock of the last pop that
        # drained this source's ring — the folded path's oldest_ts
        # upper bound (folded lanes carry no per-event timestamp)
        self._last_pop_ts = 0.0

    def start(self) -> None:
        self._lib.ig_source_start(self._h)

    def stop(self) -> None:
        self._lib.ig_source_stop(self._h)

    def close(self) -> None:
        if self._h:
            self._lib.ig_source_destroy(self._h)
            self._h = 0

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        # Stop capture (joins the thread, releases fds) but keep the native
        # handle alive: the vocab side-table must stay resolvable after the
        # window closes so labels (paths, syscall lines, comms) can still be
        # looked up from drained rows. The handle is freed on explicit
        # close() or GC.
        self.stop()

    def __del__(self):
        try:
            self.close()
        except Exception:  # lint: allow-silent-except — logging is unsafe during interpreter shutdown
            pass

    def pop(self) -> EventBatch:
        """Pop up to batch_size events; reuses one internal buffer set."""
        b = self._batch
        c = b.cols
        got = self._lib.ig_source_pop_batch(
            self._h, self.batch_size,
            _p64(c["ts"]), _p64(c["key_hash"]), _p64(c["aux1"]),
            _p64(c["aux2"]), _p64(c["mntns"]),
            _p32(c["pid"]), _p32(c["ppid"]), _p32(c["uid"]), _p32(c["kind"]),
            b.comm.ctypes.data_as(ctypes.c_char_p),
        )
        if got < 0:
            raise RuntimeError("pop on destroyed source")
        b.count = int(got)
        b.seq = self._seq
        self._seq += int(got)
        b.drops = int(self._lib.ig_source_drops(self._h))
        # batch-grain watermarks: one clock read + one vectorized min —
        # the native ts column is CLOCK_REALTIME ns, comparable with
        # time.time() epoch seconds
        b.pop_ts = time.time()
        b.oldest_ts = (float(c["ts"][: b.count].min()) / 1e9
                       if b.count else b.pop_ts)
        self._last_pop_ts = b.pop_ts
        return b

    def pop_folded(self, block: np.ndarray,
                   with_values: bool = False) -> FoldedBatch:
        """Drain the ring straight into a (3+, capacity) pre-folded SoA
        block — keys/weights/mntns uint32 lanes, filled by ONE native
        crossing (`ig_source_pop_folded`) with zero per-event Python
        work. `block` is typically a PinnedBufferPool slot wrapped
        zero-copy (np.frombuffer over the pinned mmap), so the lanes the
        C++ exporter writes ARE the H2D staging buffer: no Event structs,
        no decode, no separate fold pass. With `with_values=True` the
        block needs a 4th lane and `ig_source_pop_folded2` additionally
        fills it with the per-event magnitude (latency ns / bytes,
        saturate-cast aux1; 0 for kinds without one) — the DDSketch
        quantile plane's value lane, same single crossing."""
        need = 4 if with_values else 3
        if block.shape[0] < need or block.dtype != np.uint32:
            raise ValueError(
                f"pop_folded needs a ({need}, capacity) uint32 block")
        if with_values:
            got = self._lib.ig_source_pop_folded2(
                self._h, block.shape[1],
                _p32(block[0]), _p32(block[1]), _p32(block[2]),
                _p32(block[3]))
        else:
            got = self._lib.ig_source_pop_folded(
                self._h, block.shape[1],
                _p32(block[0]), _p32(block[1]), _p32(block[2]))
        if got < 0:
            raise RuntimeError("pop_folded on destroyed source")
        now = time.time()
        fb = FoldedBatch(lanes=block, count=int(got), seq=self._seq,
                         drops=int(self._lib.ig_source_drops(self._h)),
                         has_values=with_values,
                         pop_ts=now,
                         oldest_ts=self._last_pop_ts or now)
        self._seq += int(got)
        self._last_pop_ts = now
        return fb

    def generate(self, n: int) -> EventBatch:
        """Synchronous synthetic generation (bench path; no capture thread)."""
        b = EventBatch.alloc(n, with_comm=False)
        c = b.cols
        got = self._lib.ig_synth_generate(
            self._h, n, _p64(c["key_hash"]), _p64(c["mntns"]),
            _p32(c["pid"]), _p32(c["uid"]),
        )
        if got < 0:
            raise RuntimeError("generate on non-synthetic source")
        b.count = int(got)
        # the fast generate path fills the sketch-relevant columns only;
        # stamp kind/ts host-side
        ev_kind = {SRC_SYNTH_EXEC: 1, SRC_SYNTH_TCP: 4, SRC_SYNTH_DNS: 7}.get(
            self.kind, self.kind)
        b.cols["kind"][: b.count] = ev_kind
        b.cols["ts"][: b.count] = np.uint64(time.time_ns())
        b.pop_ts = b.oldest_ts = time.time()
        self._last_pop_ts = b.pop_ts
        return b

    def generate_folded(self, n: int, out: np.ndarray | None = None) -> np.ndarray:
        """Synchronous synthetic generation of xor-folded uint32 keys (the
        sketch plane's native width) straight into a staging buffer — no
        Event structs, no separate fold pass (bench hot path). A caller
        buffer that cannot hold n uint32 keys is an ERROR, not a silent
        fresh allocation: hot-path callers ignore the return value and
        would otherwise sketch the buffer's stale previous contents."""
        if out is None:
            out = np.empty(n, dtype=np.uint32)
        elif out.size < n or out.dtype != np.uint32:
            raise ValueError(
                f"generate_folded needs a uint32 buffer of >= {n} "
                f"entries, got {out.dtype}[{out.size}]")
        got = self._lib.ig_synth_generate_folded(self._h, n, _p32(out))
        if got < 0:
            raise RuntimeError("generate_folded on non-synthetic source")
        return out[:got]

    def drops(self) -> int:
        return int(self._lib.ig_source_drops(self._h))

    def produced(self) -> int:
        return int(self._lib.ig_source_produced(self._h))

    def set_filter(self, mntns_ids) -> None:
        """Install the capture-side mntns filter (None clears). The filter
        runs in the C++ capture thread before events reach the ring —
        the tracer-collection mntnsset-map contract."""
        if mntns_ids is None:
            self._lib.ig_source_set_filter(
                self._h, ctypes.cast(None, ctypes.POINTER(ctypes.c_uint64)), 0)
            return
        arr = np.fromiter(mntns_ids, dtype=np.uint64)
        # an empty-but-present filter blocks everything, matching an empty
        # mntns map in the reference
        if arr.size == 0:
            arr = np.zeros(1, dtype=np.uint64)
            self._lib.ig_source_set_filter(self._h, _p64(arr), 0)
            return
        self._lib.ig_source_set_filter(self._h, _p64(arr), arr.size)

    def filtered(self) -> int:
        return int(self._lib.ig_source_filtered(self._h))

    def ptrace_exit_status(self) -> int:
        return int(self._lib.ig_ptrace_exit_status(self._h))

    def vocab_lookup(self, key_hash: int) -> str:
        buf = ctypes.create_string_buffer(256)
        n = self._lib.ig_vocab_lookup(self._h, key_hash, buf, 256)
        return buf.raw[:n].decode("utf-8", "replace") if n > 0 else ""

    def vocab_lookup_batch(self, keys, stride: int = 256) -> list[str]:
        """Un-hash many keys with ONE native crossing (the display decode
        hot loop; per-row ctypes calls cost ~15us each)."""
        keys64 = np.ascontiguousarray(keys, dtype=np.uint64)
        n = keys64.size
        if n == 0:
            return []
        out = ctypes.create_string_buffer(n * stride)
        lens = np.zeros(n, dtype=np.int32)
        r = self._lib.ig_vocab_lookup_batch(
            self._h, _p64(keys64), n, out,
            stride, lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
        if r < 0:
            return [""] * n
        raw = out.raw
        ls = lens.tolist()
        return [raw[i * stride:i * stride + ls[i]].decode("utf-8", "replace")
                if ls[i] > 0 else "" for i in range(n)]
