"""Span flight recorder (mxnet_tpu/tracing.py): nesting, threading,
the disabled fast path, Chrome-trace export schema, the stall
watchdog's once-per-incident rule, and the /varz + /tracez surfaces.

Everything here drives the runtime deterministically: the watchdog is
exercised through ``tracing._sweep`` (the thread's single pass, split
out for tests) with seeded duration history, never by sleeping.
"""
import json
import threading
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry, tracing
from mxnet_tpu.gluon import nn
from mxnet_tpu.serving import ServingServer

UNITS = 16


@pytest.fixture(autouse=True)
def _trace_reset():
    """Every test starts from env-default enablement, an empty ring,
    and no watchdog; counters are process-cumulative so tests read
    deltas."""
    tracing.stop_watchdog()
    tracing._env_default()
    tracing.clear()
    yield
    tracing.stop_watchdog()
    tracing._env_default()
    tracing.clear()


def _events():
    return tracing._completed_events()


# -- span runtime ------------------------------------------------------------

def test_nested_spans_parent_chain():
    tracing.enable()
    with tracing.span("step.outer", k=1) as outer:
        with tracing.span("compile.inner") as inner:
            assert inner.parent_id == outer.span_id
    evs = {e["name"]: e for e in _events()}
    assert set(evs) == {"step.outer", "compile.inner"}
    assert evs["compile.inner"]["args"]["parent_id"] == \
        evs["step.outer"]["args"]["span_id"]
    assert evs["step.outer"]["args"]["k"] == 1
    assert evs["step.outer"]["args"].get("parent_id") is None
    # cat is the first dotted segment
    assert evs["step.outer"]["cat"] == "step"
    assert evs["compile.inner"]["cat"] == "compile"
    # the child closed first: its interval nests inside the parent's
    assert evs["compile.inner"]["ts"] >= evs["step.outer"]["ts"]
    assert (evs["compile.inner"]["ts"] + evs["compile.inner"]["dur"]
            <= evs["step.outer"]["ts"] + evs["step.outer"]["dur"] + 1)


def test_sibling_threads_have_independent_stacks():
    tracing.enable()
    ready = threading.Barrier(2)
    ids = {}

    def worker(tag):
        with tracing.span(f"step.{tag}") as sp:
            ids[tag] = sp.span_id
            ready.wait(5)          # both spans open at once
            with tracing.span("input.sub") as sub:
                ids[tag + ".sub"] = sub.parent_id

    ts = [threading.Thread(target=worker, args=(t,)) for t in ("a", "b")]
    for t in ts:
        t.start()
    for t in ts:
        t.join(10)
    # each thread's child parented to ITS OWN root, never the sibling's
    assert ids["a.sub"] == ids["a"]
    assert ids["b.sub"] == ids["b"]
    tids = {e["tid"] for e in _events() if e["name"].startswith("step.")}
    assert len(tids) == 2


def test_begin_end_cross_thread():
    """A span opened on one thread and finished on another (the serving
    request / producer-handoff shape) completes with its opener's tid
    and lands in the ring exactly once."""
    tracing.enable()
    sp = tracing.begin("serving.dispatch", batch_size=3)
    opener_tid = threading.get_ident()
    done = threading.Event()

    def closer():
        tracing.end(sp, outcome="ok")
        done.set()

    threading.Thread(target=closer).start()
    assert done.wait(5)
    evs = [e for e in _events() if e["name"] == "serving.dispatch"]
    assert len(evs) == 1
    assert evs[0]["tid"] == opener_tid
    assert evs[0]["args"]["batch_size"] == 3
    assert evs[0]["args"]["outcome"] == "ok"
    # end() is routed through finish(): a second end is a no-op
    tracing.end(sp)
    assert len([e for e in _events()
                if e["name"] == "serving.dispatch"]) == 1


def test_record_span_parents_to_current_stack():
    tracing.enable()
    t0 = time.perf_counter()
    t1 = t0 + 0.005
    with tracing.span("step.host") as sp:
        tracing.record_span("input.wait", t0, t1, queue_depth=2)
    evs = {e["name"]: e for e in _events()}
    assert evs["input.wait"]["args"]["parent_id"] == sp.span_id
    assert evs["input.wait"]["args"]["queue_depth"] == 2
    assert evs["input.wait"]["dur"] == pytest.approx(5000, rel=0.01)


def test_exception_annotates_error_and_unwinds():
    tracing.enable()
    with pytest.raises(ValueError):
        with tracing.span("step.bad"):
            raise ValueError("boom")
    ev = next(e for e in _events() if e["name"] == "step.bad")
    assert ev["args"]["error"] == "ValueError"
    # the stack unwound: a new span is a root again
    with tracing.span("step.next") as sp:
        assert sp.parent_id is None


def test_ring_buffer_overwrites_and_counts_drops(monkeypatch):
    monkeypatch.setenv("MXNET_TRACE_BUFFER", "16")
    tracing.clear()                 # re-read capacity
    tracing.enable()
    d0 = tracing.dropped_count()
    for i in range(20):
        with tracing.span("step.n", i=i):
            pass
    evs = _events()
    assert len(evs) == 16
    assert tracing.dropped_count() - d0 == 4
    # oldest → newest ordering survives the wrap
    seq = [e["args"]["i"] for e in evs]
    assert seq == list(range(4, 20))


# -- disabled fast path ------------------------------------------------------

def test_disabled_returns_shared_null_singleton():
    tracing.disable()
    a = tracing.span("step.x", k=1)
    b = tracing.begin("serving.dispatch")
    assert a is b is tracing._NULL
    with a as got:
        assert got is tracing._NULL
        a.annotate(ignored=True)
    tracing.end(b)
    tracing.record_span("input.wait", 0.0, 1.0)
    assert _events() == []
    assert tracing.open_spans() == []


def test_mxnet_trace_zero_wins_over_jsonl_and_watchdog(monkeypatch,
                                                       tmp_path):
    monkeypatch.setenv("MXNET_TRACE", "0")
    monkeypatch.setenv("MXNET_TRACE_JSONL", str(tmp_path / "t.jsonl"))
    monkeypatch.setenv("MXNET_WATCHDOG_SEC", "30")
    assert not tracing.enabled()
    assert tracing.span("step.x") is tracing._NULL
    monkeypatch.setenv("MXNET_TRACE", "1")
    assert tracing.enabled()


# -- the profiler's clock ----------------------------------------------------

def test_capture_alone_puts_the_span_on_the_host_plane(xplane_capture):
    """MXNET_TRACE unset, a capture running: the span is an annotation
    on the xplane's /host:CPU and the ring stays empty; before and after
    the capture ``span()`` is the shared no-op."""
    assert not tracing.enabled()
    assert tracing.span("decode.step") is tracing._NULL
    with xplane_capture() as found:
        with tracing.span("decode.step", slots_active=3) as sp:
            assert sp is not tracing._NULL
            sp.annotate(admitted=2)
    assert tracing.span("decode.step") is tracing._NULL
    ev, = found
    assert (ev["plane"], ev["name"]) == ("/host:CPU", "mxtpu.decode.step")
    assert ev["stats"] == {"slots_active": 3, "admitted": 2}
    assert _events() == []
    assert tracing.open_spans() == []


def test_capture_and_tracing_hold_the_span_once_each(xplane_capture):
    tracing.enable()
    with xplane_capture() as found:
        with tracing.span("step.spmd", k=1) as outer:
            outer.annotate(step=7)
            with tracing.span("step.dispatch") as inner:
                assert inner.parent_id == outer.span_id
    outer_ev, inner_ev = found
    assert (outer_ev["name"], inner_ev["name"]) == (
        "mxtpu.step.spmd", "mxtpu.step.dispatch")
    assert outer_ev["plane"] == inner_ev["plane"] == "/host:CPU"
    assert outer_ev["line"] == inner_ev["line"]
    assert outer_ev["lo"] <= inner_ev["lo"] <= inner_ev["hi"] <= \
        outer_ev["hi"]
    assert outer_ev["stats"] == {"k": 1, "step": 7}
    ring = [e["name"] for e in _events()]
    assert sorted(ring) == ["step.dispatch", "step.spmd"]
    ev = next(e for e in _events() if e["name"] == "step.spmd")
    assert ev["args"]["step"] == 7 and ev["args"]["k"] == 1


def test_after_the_fact_and_cross_thread_spans_stay_ring_only(
        xplane_capture):
    """``begin``/``end``, ``record_span`` and ``instant`` book intervals
    out of band: the profiler cannot take those, the ring does."""
    tracing.enable()
    with xplane_capture() as found:
        sp = tracing.begin("serving.request")
        t0 = time.perf_counter()
        tracing.record_span("input.wait", t0, t0 + 0.001)
        tracing.instant("decode.admit", slot=0)
        tracing.end(sp)
    assert found == []
    assert sorted(e["name"] for e in _events()) == [
        "decode.admit", "input.wait", "serving.request"]


def test_capture_alone_survives_an_exception(xplane_capture):
    with xplane_capture() as found:
        with pytest.raises(ValueError):
            with tracing.span("step.bad"):
                raise ValueError("boom")
        with tracing.span("step.next"):
            pass
    assert [e["name"] for e in found] == ["mxtpu.step.bad",
                                          "mxtpu.step.next"]


def test_mxnet_trace_zero_keeps_the_ring_off_under_a_capture(
        monkeypatch, xplane_capture):
    """MXNET_TRACE=0 turns the recorder off; a capture someone started
    still sees the program's spans."""
    monkeypatch.setenv("MXNET_TRACE", "0")
    with xplane_capture() as found:
        with tracing.span("step.spmd"):
            pass
    assert [e["name"] for e in found] == ["mxtpu.step.spmd"]
    assert _events() == []


def test_trainer_step_spans_reach_a_capture(xplane_capture):
    """``SPMDTrainer.step`` needs no span site of its own for the
    profiler: ``step.spmd`` and, inside it, ``step.dispatch`` land in a
    capture with MXNET_TRACE unset."""
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh
    net = nn.Dense(4, in_units=8)
    net.initialize()
    trainer = SPMDTrainer(net, gloss.SoftmaxCrossEntropyLoss(),
                          optimizer="sgd",
                          optimizer_params={"learning_rate": 0.1},
                          mesh=make_mesh({"dp": 1}))
    x = onp.random.randn(8, 8).astype("float32")
    y = onp.random.randint(0, 4, size=(8,)).astype("float32")
    trainer.step(x, y)                  # the compile, outside the capture
    with xplane_capture() as found:
        for _ in range(2):
            trainer.step(x, y)
    assert [e["name"] for e in found] == [
        "mxtpu.step.spmd", "mxtpu.step.dispatch"] * 2
    for outer, inner in (found[:2], found[2:]):
        assert outer["lo"] <= inner["lo"] <= inner["hi"] <= outer["hi"]
        assert outer["stats"]["step"] in (2, 3)
    assert _events() == []


# -- export / JSONL ----------------------------------------------------------

def test_export_chrome_trace_schema(tmp_path):
    tracing.enable()
    tracing.register_thread("test-main")
    with tracing.span("step.demo"):
        with tracing.span("input.wait"):
            pass
    open_sp = tracing.begin("step.stuck")    # stays open through export
    path = tracing.export(str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.load(f)
    tracing.end(open_sp)

    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert isinstance(evs, list)
    meta = [e for e in evs if e["ph"] == "M"]
    names = {e["name"] for e in meta}
    assert {"process_name", "trace_epoch_unix", "thread_name"} <= names
    assert any(e["args"].get("name") == "test-main" for e in meta
               if e["name"] == "thread_name")
    xs = [e for e in evs if e["ph"] == "X"]
    for e in xs:
        assert isinstance(e["name"], str)
        assert isinstance(e["ts"], (int, float))
        assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert "span_id" in e["args"]
        assert e["cat"] == e["name"].split(".", 1)[0]
    stuck = [e for e in xs if e["name"] == "step.stuck"]
    assert len(stuck) == 1 and stuck[0]["args"]["open"] is True


def test_jsonl_sink_streams_completed_spans(monkeypatch, tmp_path):
    sink = tmp_path / "trace.jsonl"
    monkeypatch.setenv("MXNET_TRACE_JSONL", str(sink))
    assert tracing.enabled()      # JSONL sink implies collection
    with tracing.span("step.a"):
        pass
    with tracing.span("comm.push", payload_nbytes=128):
        pass
    lines = [json.loads(ln) for ln in sink.read_text().splitlines()]
    assert [e["name"] for e in lines] == ["step.a", "comm.push"]
    assert lines[1]["args"]["payload_nbytes"] == 128


# -- stall watchdog ----------------------------------------------------------

def _seed_history(name, ms, n=8):
    with tracing._LOCK:
        tracing._durations[name] = [ms / 1e3] * n


def test_watchdog_fires_once_per_incident():
    tracing.enable()
    _seed_history("step.spmd", 1.0)         # p95 = 1 ms
    sp = tracing.begin("step.spmd")
    sp.t0 -= 1.0                            # simulate 1 s already open
    c0 = telemetry.counter("watchdog.stall_dumps").value
    fired = tracing._sweep(interval=0.01, factor=4.0)
    assert fired == [sp.span_id]
    assert telemetry.counter("watchdog.stall_dumps").value - c0 == 1
    # same incident: silent on every later sweep
    assert tracing._sweep(interval=0.01, factor=4.0) == []
    assert telemetry.counter("watchdog.stall_dumps").value - c0 == 1
    tracing.end(sp)
    # a NEW stalled span is a new incident (re-seed: the finished
    # stall itself joined the history and lifted the p95 baseline)
    _seed_history("step.spmd", 1.0)
    sp2 = tracing.begin("step.spmd")
    sp2.t0 -= 1.0
    assert tracing._sweep(interval=0.01, factor=4.0) == [sp2.span_id]
    assert telemetry.counter("watchdog.stall_dumps").value - c0 == 2
    tracing.end(sp2)


def test_watchdog_needs_history_and_scope():
    tracing.enable()
    # under _MIN_SAMPLES history: never fires (compile-heavy first
    # steps must not false-positive)
    with tracing._LOCK:
        tracing._durations["step.cold"] = [0.001] * 2
    cold = tracing.begin("step.cold")
    cold.t0 -= 5.0
    assert tracing._sweep(interval=0.01, factor=4.0) == []
    tracing.end(cold)
    # an unwatched name never fires no matter how old
    _seed_history("input.produce", 1.0)
    unwatched = tracing.begin("input.produce")
    unwatched.t0 -= 60.0
    assert tracing._sweep(interval=0.01, factor=4.0) == []
    tracing.end(unwatched)
    # below threshold = max(factor * p95, interval): no fire
    _seed_history("step.warm", 1.0)
    warm = tracing.begin("step.warm")
    assert tracing._sweep(interval=10.0, factor=4.0) == []
    tracing.end(warm)


def test_watchdog_thread_lifecycle():
    tracing.start_watchdog(seconds=0.05, factor=4.0)
    wd = tracing._watchdog
    assert wd is not None and wd.is_alive()
    tracing.stop_watchdog()
    wd.join(5.0)
    assert not wd.is_alive()
    assert tracing._watchdog is None


# -- /varz + /tracez ---------------------------------------------------------

def _make_net():
    mx.random.seed(7)
    net = nn.Sequential()
    net.add(nn.Dense(8, in_units=UNITS, activation="relu"))
    net.add(nn.Dense(4, in_units=8))
    net.initialize()
    return net


def test_varz_tracez_inprocess_roundtrip():
    tracing.enable()
    x = onp.random.RandomState(0).randn(UNITS).astype("float32")
    with ServingServer(_make_net(),
                       engine_args={"example_shape": (UNITS,),
                                    "dtype": "float32"},
                       batcher_args={"max_delay_ms": 0.0}) as srv:
        srv.predict(x)
        varz = srv.varz()
        # /varz IS the telemetry snapshot — same keys, same values
        snap = telemetry.snapshot()
        assert set(varz) == set(snap)
        assert varz["serving.requests"] == snap["serving.requests"]
        tz = srv.tracez(limit=50)
    assert tz["enabled"] is True
    assert tz["spans"] == tracing.span_count()
    names = {e["name"] for e in tz["recent"]}
    assert {"serving.enqueue", "serving.dispatch",
            "serving.request"} <= names
    disp = next(e for e in tz["recent"] if e["name"] == "serving.dispatch")
    assert disp["args"]["batch_size"] == 1
    req = next(e for e in tz["recent"] if e["name"] == "serving.request")
    assert "queue_wait_ms" in req["args"]
    assert isinstance(tz["open"], list)
    # limit caps the recent list
    assert len(srv.tracez(limit=2)["recent"]) <= 2


@pytest.mark.slow
def test_varz_tracez_http_roundtrip():
    import urllib.request
    tracing.enable()
    x = onp.random.RandomState(1).randn(UNITS).astype("float32")
    with ServingServer(_make_net(),
                       engine_args={"example_shape": (UNITS,),
                                    "dtype": "float32"},
                       batcher_args={"max_delay_ms": 0.0}) as srv:
        srv.predict(x)
        host, port = srv.start_http()
        url = f"http://{host}:{port}"
        with urllib.request.urlopen(f"{url}/varz", timeout=10) as resp:
            varz = json.loads(resp.read())
        assert varz["serving.requests"] >= 1
        with urllib.request.urlopen(f"{url}/tracez?limit=5",
                                    timeout=10) as resp:
            tz = json.loads(resp.read())
        assert tz["enabled"] is True
        assert len(tz["recent"]) <= 5
        assert {"spans", "dropped", "open"} <= set(tz)


# -- profiler integration ----------------------------------------------------

def test_profiler_counters_and_dumps_tracing_section():
    from mxnet_tpu import profiler
    tracing.enable()
    s0 = tracing.span_count()
    with tracing.span("step.demo"):
        pass
    c = profiler.counters()["tracing"]
    assert c["spans"] == s0 + 1 == tracing.span_count()
    assert {"dropped", "open", "watchdog_dumps"} <= set(c)
    out = profiler.dumps()
    assert "Trace spans" in out
    assert "step.demo" in out
