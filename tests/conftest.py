"""Test env: virtual 8-device CPU mesh + x64 for numeric-gradient checks.

Mirrors the reference's test strategy (SURVEY.md §4): multi-device tests run
against ``--xla_force_host_platform_device_count=8`` in one process, the way
the reference exercised multi-GPU op handles with several Places in one
process (details/broadcast_op_handle_test.cc).
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ["JAX_ENABLE_X64"] = "1"
# jax's persistent compilation cache stays off in tier-1 (its own switch;
# child processes inherit it): on-disk hits left by an earlier run must
# not change what the cold/warm assertions of test_compile_cache.py see.
# The tests of the cache's placement turn it back on in their children.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running integration test (subprocess clusters, "
        "convergence runs)")
    config.addinivalue_line(
        "markers", "chaos_lite: tier-1-safe chaos scenarios (one "
        "kill-promote pserver run + master lease-replay); the full flap "
        "matrix stays slow")
