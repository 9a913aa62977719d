"""Device acquisition for every process that will touch the device plane.

The chip belongs to whichever process initialises it first, so acquisition
happens IN this process: ask JAX for its devices, report what it found,
and fail when that is not what was asked for. No child process probes the
chip (a second initialisation per start, and a child killed mid-init can
leave the chip held), nothing retries, and nothing falls back: a caller
that asked for the TPU and did not get one gets `PlatformUnavailable`,
never a CPU run under another name. The outcome lands in the telemetry
registry, the flight recorder, and doctor output.
"""

from __future__ import annotations

import threading
import time

from ..telemetry.registry import gauge
from ..telemetry.tracing import RECORDER, TRACER

PLATFORMS = ("auto", "tpu", "cpu")

_tm_info = gauge("ig_platform_info", "acquired device platform (1=current)",
                 ("platform",))

# last acquire_platform outcome, for doctor/flight-record rendering
_last_acquire: dict | None = None
_mu = threading.Lock()


class PlatformUnavailable(RuntimeError):
    """The requested device platform is not what JAX initialised."""


def last_acquire() -> dict | None:
    with _mu:
        return dict(_last_acquire) if _last_acquire else None


def acquire_platform(requested: str = "auto") -> dict:
    """Resolve `--platform auto|tpu|cpu` before first device use.

    cpu: pin this process to the CPU backend. tpu: the first device must
    be a TPU, else PlatformUnavailable. auto: whatever JAX itself
    reports — the CPU only when JAX found no accelerator.
    Returns {requested, platform, device_kind, device_count, detail,
    elapsed}.
    """
    if requested not in PLATFORMS:
        raise ValueError(f"platform must be auto|tpu|cpu, not {requested!r}")
    import jax
    with TRACER.span("platform/acquire", attrs={"requested": requested}):
        t0 = time.perf_counter()
        pinned = jax.config.jax_platforms or ""
        if requested == "cpu":
            jax.config.update("jax_platforms", "cpu")
        elif requested == "tpu" and not pinned:
            # nothing pinned from outside: make JAX fail with its own
            # reason instead of quietly skipping a TPU it cannot open
            jax.config.update("jax_platforms", "tpu")
        try:
            devices = jax.devices()
        except RuntimeError as e:
            raise PlatformUnavailable(
                f"platform {requested} requested but JAX could not "
                f"initialise it: {e}") from e
        elapsed = time.perf_counter() - t0
        platform = devices[0].platform
        if requested != "auto" and platform != requested:
            raise PlatformUnavailable(
                f"platform {requested} requested but JAX reports "
                f"{platform!r} (JAX_PLATFORMS={pinned!r}, "
                f"{len(devices)} device(s))")
        out = {"requested": requested, "platform": platform,
               "device_kind": devices[0].device_kind,
               "device_count": len(devices),
               "detail": f"{len(devices)} x {devices[0].device_kind} "
                         f"in {elapsed:.1f}s",
               "elapsed": elapsed}
    _tm_info.labels(platform=platform).set(1.0)
    RECORDER.set_fact("platform", platform)
    RECORDER.set_fact("platform_acquire", out)
    global _last_acquire
    with _mu:
        _last_acquire = out
    return out
