"""Test configuration: force an 8-device virtual CPU mesh.

Mirrors the reference's "distributed without a cluster" strategy (Spark tests
run local[N] in-JVM, BaseSparkTest.java:89): multi-chip sharding is exercised
on N virtual CPU devices via --xla_force_host_platform_device_count, so the
full tp/dp test matrix runs on any host. The chip is exercised by
chip_smoke.py through the chip tool, not by the test suite.

The suite pins the platform itself (jax.config.update below, before the
backend initialises) so it runs the same whatever JAX_PLATFORMS says, and
hard-asserts the device count so a silent single-device mesh can never fake
a passing distributed suite.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
assert jax.device_count() == 8, (
    f"Test suite requires the 8-device virtual CPU mesh, got "
    f"{jax.devices()} — platform forcing failed")

import pytest  # noqa: E402


@pytest.fixture
def rng_key():
    return jax.random.PRNGKey(0)
