"""Test configuration: force CPU and an 8-device host-platform mesh.

No accelerator is used by the tests: `jax.config.update("jax_platforms",
"cpu")` pins the platform in-process and JAX_PLATFORMS=cpu pins it for every
subprocess a test spawns. The sharding tests run on 8 host-platform devices
(`--xla_force_host_platform_device_count=8`), which XLA_FLAGS must carry
before the first backend initialization (the CPU client reads it at
creation).
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"  # for any subprocesses tests spawn

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _lockcheck_gate():
    """Concurrency-correctness gate (analysis/lockcheck.py): when the
    suite runs with BCOS_LOCKCHECK=1, every hot lock in the tree is the
    instrumented wrapper, and the whole tier-1 run must finish with ZERO
    lock-order cycles, canonical-order violations, blocking-while-locked
    hits and self-deadlocks. Disarmed runs (the default) pay nothing —
    the factories hand out plain threading primitives."""
    from fisco_bcos_tpu.analysis import lockcheck

    if not lockcheck.armed():
        yield
        return
    lockcheck.reset()
    yield
    # tests that INTENTIONALLY provoke violations (tests/test_lockcheck.py)
    # reset the plane in their teardown, so anything left here is real
    lockcheck.assert_clean()
