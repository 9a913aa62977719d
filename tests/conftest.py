"""Test configuration: force an 8-device virtual CPU platform before JAX use.

Mirrors the reference's test strategy of kernel-real-but-container-free unit
tests (reference internal/test/runner.go:103-218 unshares namespaces to fake
containers); here the analogue is a virtual 8-device CPU mesh standing in for
a TPU pod slice so sharding/psum paths are exercised without TPU hardware.

The persistent compilation cache is off for the tests and for every child
they start (utils/compile_cache.py would otherwise point them all at the
checkout's `.jax_cache`): a test run must neither read executables an
earlier tree compiled nor leave CPU entries in the chip's cache.
"""

import os

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
