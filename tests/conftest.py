"""Test configuration: force an 8-device virtual CPU mesh.

Parity with the reference test strategy (SURVEY.md §4): multi-device
tests run on emulated devices (xla_force_host_platform_device_count),
the way the reference emulates clusters with --launcher local.  The
platform and the device count are set through the environment before
jax is imported.

It also holds what the tier-1 run is run under (``tests/README.md``):
one compile cache a run, a file with a shared fixture whole on one
worker, one bound on every test's call, no batcher left open.
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

import contextlib
import faulthandler
import functools
import re
import shutil
import signal
import sys
import tempfile

import numpy as _onp
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: second-tier tests excluded from the tier-1 run "
        "(ROADMAP.md runs -m 'not slow')")
    _share_what_is_compiled(config)


_COMPILE_CACHE = pytest.StashKey[str]()     # the directory, where made


def _share_what_is_compiled(config):
    """One compile cache for the run, empty at its start, shared by its
    workers and gone at its end.

    Most of the run is XLA compiling small programs that another test,
    or another worker, has compiled already: an eager operator's, a
    jitted core's under a fresh ``jax.jit``, an engine's executables.
    With JAX's persistent cache on a directory of the run's own, holding
    every program however short its compile, the second compile of a
    program is a read.  A caller that set ``JAX_COMPILATION_CACHE_DIR``
    owns the placement, and a run on a chip is left alone."""
    if _ON_TPU or os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    worker = getattr(config, "workerinput", None)
    if worker is not None:
        path = worker["compile_cache"]
    else:
        path = config.stash[_COMPILE_CACHE] = tempfile.mkdtemp(
            prefix="mxtpu_tests_compile_cache_")
        if config.getoption("numprocesses", None):
            return          # the controller runs no test: no jax here
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


@pytest.hookimpl(optionalhook=True)
def pytest_configure_node(node):
    node.workerinput["compile_cache"] = node.config.stash[_COMPILE_CACHE]


def pytest_unconfigure(config):
    path = config.stash.get(_COMPILE_CACHE, None)
    if path is not None:
        shutil.rmtree(path, ignore_errors=True)


_TESTS_DIR = os.path.dirname(os.path.abspath(__file__))


@functools.lru_cache(maxsize=None)
def _shares_fixtures(filename):
    """Whether the test file ``filename`` of this directory builds
    something once for several of its tests: a fixture of module or
    session scope."""
    try:
        with open(os.path.join(_TESTS_DIR, filename)) as f:
            return re.search(r'scope=["\'](module|session)["\']',
                             f.read()) is not None
    except OSError:
        return False


def _work_unit(nodeid):
    """What the scheduler hands to ONE worker together: the whole file
    where its tests share a fixture, else the single test."""
    path = nodeid.split("::", 1)[0]
    return path if _shares_fixtures(os.path.basename(path)) else nodeid


@pytest.hookimpl(optionalhook=True)
def pytest_xdist_make_scheduler(config, log):
    """``--dist load`` deals single tests to the workers, so a module
    fixture is built once per worker that is dealt a test of its file:
    a model and its compiled executables up to six times a run.  This
    keeps a file that has such a fixture whole on one worker (the
    largest files start first) and still deals every other test
    singly.  Any other ``--dist`` is left as asked for."""
    if config.getvalue("dist") != "load":
        return None
    from xdist.scheduler import LoadScopeScheduling

    class SharedFixturesOnOneWorker(LoadScopeScheduling):
        def _split_scope(self, nodeid):
            return _work_unit(nodeid)

    return SharedFixturesOnOneWorker(config, log)


# The one bound on a test's call, in seconds: four times the slowest
# test of the tier-1 run, and no option changes it.  A test that hangs
# or waits on a clock costs one failure with its name and every
# thread's stack, not the run (the driver's `timeout` kills the run
# whole and its log does not say where it was).
_TEST_BOUND_S = 240


@contextlib.contextmanager
def _bounded(nodeid):
    """Fail the test ``nodeid`` when the body outlasts ``_TEST_BOUND_S``.

    The alarm's handler runs in the main thread, where pytest (and an
    xdist worker) calls the test: it writes every thread's stack to the
    real stderr, which the test's report captures, and raises.  Where the
    main thread sits in native code and never gets to run the handler,
    the watchdog thread of ``faulthandler`` writes the stacks half a
    minute later and ends the process: xdist names the test the worker
    died in and replaces the worker."""
    bound = _TEST_BOUND_S

    def late(signum, frame):
        faulthandler.dump_traceback(file=sys.__stderr__, all_threads=True)
        pytest.fail(f"{nodeid} passed the bound of {bound} s on a test's "
                    "call (tests/conftest.py, _TEST_BOUND_S)", pytrace=False)

    was = signal.signal(signal.SIGALRM, late)
    left = signal.alarm(bound)
    faulthandler.dump_traceback_later(bound + 30, exit=True,
                                      file=sys.__stderr__)
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()
        signal.alarm(left)
        signal.signal(signal.SIGALRM, was)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    # a chip's first compile of a real size has no bound here
    with contextlib.nullcontext() if _ON_TPU else _bounded(item.nodeid):
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


@pytest.fixture(autouse=True)
def _no_batcher_outlives_its_test():
    """A test that fails before it closes its ``DynamicBatcher`` would
    leave it registered with ``serving.slo`` and live; the next test on
    that worker that escalates a ``queue_saturation`` incident then
    tunes the stray batcher and reads its sizes, not its own."""
    yield
    slo = sys.modules.get("mxnet_tpu.serving.slo")
    if slo is not None:
        for batcher in list(slo._batchers):
            if not batcher.closed:
                batcher.close(drain=False)


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
