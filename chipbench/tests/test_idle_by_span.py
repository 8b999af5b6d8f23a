"""``readers/trace_idle_by_span.py`` on a synthetic parsed trace: the
host's clock is found from the trace and applied before a gap is
booked."""
import json
import os

import pytest

from chipbench.harness import named
from chipbench.harness.cli import BENCH_DIR, layer_metrics, load_module

MS = 1e-3
OFFSET = 0.8 * MS           # host clock minus device clock


@pytest.fixture(scope="module")
def reader():
    return load_module("readers", "trace_idle_by_span")


def _parsed(offset=OFFSET, sync_offset=None, earlier_runs=0):
    """Two busy stretches of chained turns around an empty server, on
    the device's clock in ms; the host's spans then moved by ``offset``
    (its reads by ``sync_offset``, where a test wants them wrong).

    A turn dispatches a run and then reads the run before.  A first
    turn's run begins 0.1 ms after its dispatch does (the device stood
    idle); a read ends 0.1 ms after its run.  The device idles inside
    run 1 for 1 ms (under that turn's ``decode.sync``), for 0.9 ms
    after run 2 before a stray operation (0.45 ms past the last turn's
    end: under no span), and then for 31.3 ms under six waits of an
    empty server."""
    runs = [(10.0, 16.0), (16.0, 22.0), (22.0, 28.0), (60.3, 66.3),
            (66.3, 72.3)]
    ops = [(10.0, 16.0), (16.0, 18.0), (19.0, 22.0), (22.0, 28.0),
           (28.9, 29.0), (60.3, 66.3), (66.3, 72.3)]
    # a turn: (step, dispatch or None, sync or None)
    turns = [((9.8, 10.5), (9.9, 10.4), None),
             ((10.5, 16.2), (10.6, 11.1), (11.1, 16.1)),
             ((16.2, 22.2), (16.3, 16.8), (16.8, 22.1)),
             ((22.2, 28.2), None, (22.3, 28.1)),
             ((60.1, 60.8), (60.2, 60.7), None),
             ((60.8, 66.5), (60.9, 61.4), (61.4, 66.4)),
             ((66.5, 72.5), None, (66.6, 72.4))]
    spans = []
    for step, dispatch, sync in turns:
        spans.append((*step, "mxtpu.decode.step", offset))
        if dispatch:
            spans.append((*dispatch, "mxtpu.decode.decode", offset))
        if sync:
            spans.append((*sync, "mxtpu.decode.sync",
                          offset if sync_offset is None else sync_offset))
    spans += [(30.0 + 5 * k, 35.0 + 5 * k, "mxtpu.decode.empty", offset)
              for k in range(6)]
    # runs the trace holds of turns from before the capture
    head = [(10.0 - 6 * (k + 1), 10.0 - 6 * k) for k in range(earlier_runs)]
    runs = sorted(head) + runs
    return {
        "span_prefix": "mxtpu.",
        "devices": [{"plane": "/device:TPU:0", "n": 0, "kernels": [],
                     "ops": [(a * MS, b * MS) for a, b in sorted(head) + ops],
                     "modules": [(a * MS, b * MS, "jit_mxtpu_decode(77)")
                                 for a, b in runs]
                     + [(28.9 * MS, 29.0 * MS, "jit_mxtpu_state_edit(5)")]}],
        "spans": sorted((a * MS + off, b * MS + off, name, "sched/1")
                        for a, b, name, off in spans)}


@pytest.mark.parametrize("earlier_runs", [0, 1, 2])
def test_offset_recovered_from_the_two_bounds(reader, earlier_runs):
    lower, upper, shift = reader.offset_bounds(
        _parsed(earlier_runs=earlier_runs))
    assert shift == earlier_runs
    assert lower == pytest.approx(0.7 * MS) and upper == pytest.approx(
        0.9 * MS)
    assert (lower + upper) / 2 == pytest.approx(OFFSET)


def test_gaps_are_booked_by_the_span_over_them_on_the_devices_clock(reader):
    parsed = _parsed()
    window = named.window(parsed)
    assert window == pytest.approx((10.0 * MS, 72.3 * MS))
    shares = reader.idle_shares(parsed, OFFSET)
    assert shares["decode.empty"] == pytest.approx(100 * 31.3 / 62.3)
    # the hole inside run 1 lies under that turn's decode.sync
    assert shares["decode.step"] == pytest.approx(100 * 1.0 / 62.3)
    idle = 100 * sum(b - a for a, b in named.idle_intervals(parsed)) \
        / (window[1] - window[0])
    assert idle == pytest.approx(100 * 33.2 / 62.3)
    # what lies under no span is the rest
    assert idle - sum(shares.values()) == pytest.approx(100 * 0.9 / 62.3)
    # a turn that began before the capture leaves its phases in the
    # trace without its own span: the hole under its sync is still a turn's
    cut = dict(parsed, spans=[sp for sp in parsed["spans"] if not (
        sp[2] == "mxtpu.decode.step" and sp[0] < 19 * MS < sp[1])])
    assert len(cut["spans"]) == len(parsed["spans"]) - 1
    assert reader.idle_shares(cut, OFFSET) == pytest.approx(shares)
    # on the host's own clock the 0.9 ms would be the last turn's
    wrong = reader.idle_shares(parsed, 0.0)
    assert wrong["decode.step"] == pytest.approx(100 * 1.9 / 62.3)


def test_crossed_bounds_book_nothing(reader):
    # reads that end 5 ms before the runs they waited for ended
    parsed = _parsed(sync_offset=OFFSET - 5 * MS)
    assert reader.offset_bounds(parsed) is None
    obs = {"_named": parsed,
           "notes": {"engine": {"sched": {"turns": 7}}}}
    assert reader.read(obs, span="decode.empty",
                       known_by="sched.turns") is None
    assert reader.read(obs, span="decode.step",
                       known_by="sched.turns") is None


def test_the_metric_files_read_it_and_a_program_without_the_span_reads_none(
        reader, capsys):
    names = ["serve_idle_empty_share", "serve_idle_host_share"]
    for name in names:
        with open(os.path.join(BENCH_DIR, "layer_metrics",
                               f"{name}.json")) as f:
            assert json.load(f)["reader"] == "trace_idle_by_span"
    obs = {"_named": _parsed(),
           "notes": {"engine": {"sched": {"turns": 7}}}}
    got = layer_metrics(obs, names)
    assert got["serve_idle_empty_share"] == {
        "value": pytest.approx(100 * 31.3 / 62.3), "unit": "%"}
    assert got["serve_idle_host_share"]["value"] == pytest.approx(
        100 * 1.0 / 62.3)
    said = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    offset, = [s for s in said if s["chipbench"] == "clock_offset"]
    assert offset["bounds_ms"] == pytest.approx([0.7, 0.9])
    # the span never occurred in a program that has it: 0, not nothing
    busy = _parsed()
    busy["spans"] = [s for s in busy["spans"]
                     if s[2] != "mxtpu.decode.empty"]
    obs = {"_named": busy, "notes": {"engine": {"sched": {"turns": 7}}}}
    assert layer_metrics(obs, names)["serve_idle_empty_share"]["value"] == 0.0
    # the parent's engine has no turn clock, so its program has no
    # decode.empty: nothing, and no error
    assert layer_metrics({"_named": _parsed(),
                          "notes": {"engine": {"compiles": 0}}}, names) == {}
    # an untraced run, and a trace with no device plane (a rehearsal)
    assert layer_metrics({"_named": None, "notes": {}}, names) == {}
    assert layer_metrics({"_named": {"devices": [], "spans": [],
                                     "span_prefix": "mxtpu."},
                          "notes": {"engine": {"sched": {"turns": 7}}}},
                         names) == {}
