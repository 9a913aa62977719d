"""Provenance stamping: who/where/what produced a perf number.

Every PerfRecord carries the git sha (+dirty flag), a host fingerprint
and the acquired platform — so a record read months later still answers
"was this a real TPU run?" without trusting surrounding prose.
"""

from __future__ import annotations

import os
import platform as _platform
import socket
import subprocess
import sys

_GIT_TIMEOUT = 10.0


def git_provenance(cwd: str | None = None) -> tuple[str, bool]:
    """(sha, dirty). 'unknown' when not in a git checkout — recorded as
    such rather than guessed."""
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=cwd, capture_output=True,
            text=True, timeout=_GIT_TIMEOUT).stdout.strip()
    except (OSError, subprocess.TimeoutExpired):
        sha = ""
    if not sha:
        return "unknown", False
    try:
        dirty = bool(subprocess.run(
            ["git", "status", "--porcelain"], cwd=cwd, capture_output=True,
            text=True, timeout=_GIT_TIMEOUT).stdout.strip())
    except (OSError, subprocess.TimeoutExpired):
        dirty = False
    return sha, dirty


def host_fingerprint() -> dict:
    return {
        "hostname": socket.gethostname() or "unknown",
        "machine": _platform.machine() or "unknown",
        "python": sys.version.split()[0],
        "cpu_count": os.cpu_count() or 0,
    }


def build_provenance(platform: str, probe: dict | None = None,
                     cwd: str | None = None) -> dict:
    """Assemble the provenance block from the platform the run got, the
    probe block of its acquisition (probe_block) and repo + host facts."""
    sha, dirty = git_provenance(cwd)
    return {
        "git_sha": sha,
        "git_dirty": dirty,
        "host": host_fingerprint(),
        "platform": platform if platform in ("tpu", "cpu", "gpu", "none")
        else "unknown",
        # a run that does not get the platform it asked for fails
        # (utils/platform_probe), so nothing stamped here is degraded;
        # the schema keeps the field for the imported records that are
        "degraded": False,
        "probe": probe or probe_block(None),
    }


def probe_block(acquired: dict | None) -> dict:
    """The record's provenance.probe block from an acquire_platform
    outcome (None: the run acquired no device of its own)."""
    if not acquired:
        return {"outcome": "unprobed"}
    return {
        "outcome": "ok",
        "requested": acquired.get("requested", ""),
        "detail": acquired.get("detail", ""),
        "elapsed_s": round(float(acquired.get("elapsed", 0.0)), 3),
    }


def acquire_provenance(requested: str = "auto") -> dict:
    """Acquire the device in this process (utils/platform_probe: a TPU
    asked for and absent raises) and stamp the provenance block every
    device-touching micro-bench shares."""
    from ..utils.compile_cache import ensure_compile_cache
    from ..utils.platform_probe import acquire_platform
    ensure_compile_cache()
    acquired = acquire_platform(requested)
    return build_provenance(acquired["platform"], probe_block(acquired))
