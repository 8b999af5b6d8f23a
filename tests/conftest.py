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


@pytest.fixture
def xplane_capture(tmp_path):
    """``with xplane_capture() as found:`` runs a JAX profiler capture
    on the CPU.  Once the ``with`` is left, ``found`` holds one dict per
    host event the program's own spans left in the xplane (names that
    start with ``mxtpu.``): ``plane``, ``line`` (the thread), ``name``,
    ``stats``, ``lo`` and ``hi`` in ns, in order of start."""
    import contextlib
    import glob

    import jax

    @contextlib.contextmanager
    def capture():
        found = []
        jax.profiler.start_trace(str(tmp_path))
        try:
            yield found
        finally:
            jax.profiler.stop_trace()
        path = max(glob.glob(os.path.join(
            str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")),
            key=os.path.getmtime)
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("mxtpu."):
                        found.append({
                            "plane": plane.name, "line": line.name,
                            "name": ev.name, "stats": dict(ev.stats),
                            "lo": ev.start_ns,
                            "hi": ev.start_ns + ev.duration_ns})
        found.sort(key=lambda e: (e["lo"], -e["hi"]))

    return capture
