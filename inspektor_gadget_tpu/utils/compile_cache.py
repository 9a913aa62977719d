"""One persistent XLA compilation cache for every entry point.

A whole ingest step costs most of a minute to compile for the chip and a
run meets several pad shapes, so every process that will touch the device
(CLI, `agent.main serve`, chip_smoke.py, the benchmark) calls
`ensure_compile_cache()` before its first compile. Where
`JAX_COMPILATION_CACHE_DIR` is set JAX already uses that directory and
no directory is set in code. Otherwise the cache is ONE fixed directory
inside the checkout — the path is part of the cache key's locality, so
never a temporary name, a pid or a time.

The key must also survive an edit. JAX strips source locations from a
program before hashing it, but a Pallas kernel's body travels inside the
program as an opaque serialized string that keeps its own locations, and
with full tracebacks those name every Python frame above the kernel. So
one shifted line in ANY caller (the operator, the smoke) changed the key
of every program that holds a kernel — both update paths do on a TPU —
and each chip run recompiled them all at about 28 s apiece (PR 21: five
calls in a row hit nothing, two calls of an unchanged tree hit
everything). Locations therefore carry the innermost frame only.
"""

from __future__ import annotations

import os
import threading
from pathlib import Path

from ..telemetry import counter

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

# JAX's own monitoring events, counted where an operator of a live agent
# can read them: a program that reaches the backend mid-run stalls the
# loop on a thread whose CPU time the loop's own accounting cannot see
_tm_compiles = counter(
    "ig_jax_backend_compiles_total",
    "programs handed to the backend compiler (a persistent-cache read "
    "counts too: see ig_jax_compile_cache_hits_total)")
_tm_compile_s = counter(
    "ig_jax_backend_compile_seconds_total",
    "seconds inside backend compiles, cache reads included")
_tm_cache_hits = counter(
    "ig_jax_compile_cache_hits_total",
    "backend compiles answered by the persistent compilation cache")
_listen_mu = threading.Lock()
_listening = False


def _on_duration(name: str, secs: float, **_kw) -> None:
    if name == "/jax/core/compile/backend_compile_duration":
        _tm_compiles.inc()
        _tm_compile_s.inc(secs)


def _on_event(name: str, **_kw) -> None:
    if name == "/jax/compilation_cache/cache_hits":
        _tm_cache_hits.inc()


def ensure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return it; the first call also starts counting compiles. Idempotent;
    call before the first compile."""
    import jax
    global _listening
    with _listen_mu:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            jax.monitoring.register_event_listener(_on_event)
            _listening = True
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
