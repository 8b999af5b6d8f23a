"""The rules the tier-1 run itself is held to (``tests/README.md``):
the one bound on a test's call, which files stay whole on one worker,
and what a test may not leave behind for the next one."""
import sys
import time

import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon import nn
from mxnet_tpu.serving import DynamicBatcher, InferenceEngine, slo


def _conftest():
    return sys.modules["conftest"]


def test_a_test_that_sleeps_past_the_bound_fails_by_name_with_a_stack(
        monkeypatch, capfd):
    monkeypatch.setattr(_conftest(), "_TEST_BOUND_S", 1)
    began = time.monotonic()
    with pytest.raises(pytest.fail.Exception,
                       match=r"tests/test_x\.py::test_sleeps passed the "
                             r"bound of 1 s"):
        with _conftest()._bounded("tests/test_x.py::test_sleeps"):
            time.sleep(20)
    assert time.monotonic() - began < 5
    stack = capfd.readouterr().err
    assert "most recent call first" in stack
    assert "test_suite_rules.py" in stack


def test_the_bound_is_four_times_the_slowest_test_and_two_minutes_at_least():
    # no tier-1 test may take more than 60 s in the driver's junit file
    assert _conftest()._TEST_BOUND_S >= max(120, 4 * 60)


@pytest.mark.parametrize("nodeid,unit", [
    # module fixtures hold models and compiled engines: the file is ONE
    # unit of work, built once whatever the number of workers
    ("tests/test_decode.py::test_eos_stops_generation",
     "tests/test_decode.py"),
    ("tests/test_prefill_lanes.py::test_lanes_come_from_shapes[a-b]",
     "tests/test_prefill_lanes.py"),
    # nothing shared: every test is dealt alone
    ("tests/test_examples.py::test_vae_example",
     "tests/test_examples.py::test_vae_example"),
    ("tests/test_legacy_serialization.py::TestGluonLoad::test_x",
     "tests/test_legacy_serialization.py::TestGluonLoad::test_x"),
    ("tests/no_such_file.py::test_x", "tests/no_such_file.py::test_x"),
])
def test_a_file_with_a_shared_fixture_is_one_unit_of_work(nodeid, unit):
    assert _conftest()._work_unit(nodeid) == unit


def test_a_batcher_left_open_is_closed_behind_its_test():
    net = nn.Dense(4, in_units=8)
    net.initialize()
    eng = InferenceEngine(net, example_shape=(8,), dtype="float32")
    stray = DynamicBatcher(eng, start=False, max_batch_size=32)
    assert stray in slo._batchers and not stray.closed
    fixture = _conftest()._no_batcher_outlives_its_test.__wrapped__()
    next(fixture)
    assert not stray.closed              # the test's own body runs here
    with pytest.raises(StopIteration):
        next(fixture)
    assert stray.closed
