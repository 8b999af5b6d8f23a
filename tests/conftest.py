"""Test configuration: force an 8-device virtual CPU mesh.

Parity with the reference test strategy (SURVEY.md §4): multi-device
tests run on emulated devices (xla_force_host_platform_device_count),
the way the reference emulates clusters with --launcher local.  The
platform and the device count are set through the environment before
jax is imported.
"""
import os

# MXNET_TEST_ON_TPU=1: run the suite on whatever real accelerator the
# machine exposes instead of the virtual CPU mesh.  Interpret-mode
# pallas and CPU lowering skip real-TPU constraints (block-spec tiling,
# MXU default precision), so a targeted pass on a chip catches what
# the CPU suite cannot (tests/test_tpu_compile.py covers the compile
# side without a chip).  Tests needing more
# devices than the host has are converted to skips by the
# pytest_runtest_call hook below (make_mesh raises ValueError on a
# device shortage; on a 1-chip host that is expected, not a failure).
_ON_TPU = os.environ.get("MXNET_TEST_ON_TPU", "") == "1"

if not _ON_TPU:
    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = \
            (_flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as _onp
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: second-tier tests excluded from the tier-1 run "
        "(ROADMAP.md runs -m 'not slow')")


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    outcome = yield
    if _ON_TPU and outcome.excinfo is not None:
        etype, evalue = outcome.excinfo[0], outcome.excinfo[1]
        if issubclass(etype, ValueError) and \
                "devices, have" in str(evalue):
            outcome.force_exception(
                pytest.skip.Exception(
                    f"needs more devices than this host has: {evalue}"))


@pytest.fixture(autouse=True)
def _seed_everything():
    """Deterministic seeds per test (parity: with_seed() decorator,
    tests/python/unittest/common.py:163; MXNET_TEST_SEED overrides,
    which is what tools/flakiness_checker.py varies)."""
    import os
    seed = int(os.environ.get("MXNET_TEST_SEED", "0"))
    _onp.random.seed(seed)
    import mxnet_tpu as mx
    mx.random.seed(seed)
    yield
