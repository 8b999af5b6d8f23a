"""The trace under the program's own names (harness/named.py, the
``trace_named`` reader, its metric files and ``tools/named_report.py``)
against the trace recorded on a TPU v5e kept beside this file and a
synthetic one written as a text proto below, numbers worked out by hand."""
import json
import os
import re
import subprocess
import sys
import time

import pytest

from chipbench.harness import named, trace
from chipbench.harness.cli import (BENCH_DIR, REPO_DIR, layer_metrics,
                                   load_module)

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "v5e_1chip.xplane.pb")
US, NS = 1e-6, 1e-9

# Times in microseconds from the session's start.
#
# TPU:0  XLA Modules  jit_mxtpu_decode(11)        [  0,  40)  [100, 150)
#                     jit_mxtpu_prefill_b128(22)  [ 50,  70)  [ 70,  80)
#                     jit_mxtpu_prefill_b64(33)   [ 80,  95)
#        XLA Ops      fusion.1                    [  0,  20)  [100, 120)
#                     custom-call.7 ConcatBitcast [ 10,  12)  not Mosaic
#                     mxtpu_rope.3                [ 20,  25)
#                     mxtpu_paged_attention.4     [ 25,  40)  [120, 150)
#                     mxtpu_rope.5                [ 50,  56)
#                     fusion.2                    [ 56,  95)
#                     while.9                     [100, 150)  container
#   busy = [0,40) + [50,95) + [100,150) = 135 of a 150 us window
#   idle = [40,50) and [95,100); rope = 5 + 6 = 11; paged = 15 + 30 = 45
# TPU:1  XLA Ops      fusion.1 [0, 30), mxtpu_rope.3 [30, 39): busy 39
#   over both: busy 174, rope 20, paged 45, every Mosaic call 65
#   decode runs 40 and 50 us (median 45); prefill runs 20, 10, 15
#   (median 15); 3 prefill runs for 2 decode runs
#
# host, thread "sched":
#   mxtpu.decode.step      [  0,  98)   wholly inside the window
#     mxtpu.decode.prefill [ 45,  96)
#       mxtpu.decode.stage [ 45,  49)
#       mxtpu.decode.sync  [ 90,  96)
#     mxtpu.decode.account [ 96,  98)
#   mxtpu.decode.step      [ 99, 160)   ends after the last operation
#     mxtpu.decode.account [155, 160)
# host, thread "other": mxtpu.decode.stage [46, 47), PjitFunction [0, 10)
#   prefill less its own thread's stage and sync = 51 - 4 - 6 = 41
#   idle inside the one whole turn = [40,50) + [95,98) = 13 us
#   gap [40,50): middle 45 lies in step, prefill and sched's stage
#   [45,49), the shortest; gap [95,100): middle 97.5 in step and account
SYNTHETIC = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 7 offset_ps: 10000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 20000000 duration_ps: 5000000 }
    events { metadata_id: 4 offset_ps: 25000000 duration_ps: 15000000 }
    events { metadata_id: 5 offset_ps: 50000000 duration_ps: 6000000 }
    events { metadata_id: 2 offset_ps: 56000000 duration_ps: 39000000 }
    events { metadata_id: 9 offset_ps: 100000000 duration_ps: 50000000 }
    events { metadata_id: 1 offset_ps: 100000000 duration_ps: 20000000 }
    events { metadata_id: 4 offset_ps: 120000000 duration_ps: 30000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 11 offset_ps: 0 duration_ps: 40000000 }
    events { metadata_id: 22 offset_ps: 50000000 duration_ps: 20000000 }
    events { metadata_id: 22 offset_ps: 70000000 duration_ps: 10000000 }
    events { metadata_id: 33 offset_ps: 80000000 duration_ps: 15000000 }
    events { metadata_id: 11 offset_ps: 100000000 duration_ps: 50000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.2" } }
  event_metadata { key: 3 value { id: 3 name: "%mxtpu_rope.3 = bf16[96,16,64]{2,1,0:T(8,128)(2,1)} custom-call(bf16[96,16,64]{2,1,0} %p.1, s32[96,128]{1,0} %p.2), custom_call_target=\\"tpu_custom_call\\", frontend_attributes={}" } }
  event_metadata { key: 4 value { id: 4 name: "%mxtpu_paged_attention.4 = bf16[96,1,1024]{2,1,0:T(8,128)(2,1)} custom-call(s32[96,64]{1,0} %t), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 5 value { id: 5 name: "%mxtpu_rope.5 = bf16[128,16,64]{2,1,0:T(8,128)(2,1)} custom-call(bf16[128,16,64]{2,1,0} %p.3), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 7 value { id: 7 name: "%custom-call.7 = f32[8]{0:T(8)S(1)} custom-call(f32[4]{0} %a, f32[4]{0} %b), custom_call_target=\\"ConcatBitcast\\"" } }
  event_metadata { key: 9 value { id: 9 name: "while.9" } }
  event_metadata { key: 11 value { id: 11 name: "jit_mxtpu_decode(11)" } }
  event_metadata { key: 22 value { id: 22 name: "jit_mxtpu_prefill_b128(22)" } }
  event_metadata { key: 33 value { id: 33 name: "jit_mxtpu_prefill_b64(33)" } }
}
planes {
  id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 30000000 }
    events { metadata_id: 3 offset_ps: 30000000 duration_ps: 9000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 3 value { id: 3 name: "%mxtpu_rope.3 = bf16[96,16,64]{2,1,0} custom-call(bf16[96,16,64]{2,1,0} %p.1), custom_call_target=\\"tpu_custom_call\\"" } }
}
planes {
  id: 3 name: "/host:CPU"
  lines { id: 1 name: "sched" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 98000000 }
    events { metadata_id: 2 offset_ps: 45000000 duration_ps: 51000000 }
    events { metadata_id: 3 offset_ps: 45000000 duration_ps: 4000000 }
    events { metadata_id: 4 offset_ps: 90000000 duration_ps: 6000000 }
    events { metadata_id: 5 offset_ps: 96000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 99000000 duration_ps: 61000000 }
    events { metadata_id: 5 offset_ps: 155000000 duration_ps: 5000000 }
  }
  lines { id: 2 name: "other" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 46000000 duration_ps: 1000000 }
    events { metadata_id: 6 offset_ps: 0 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "mxtpu.decode.step" } }
  event_metadata { key: 2 value { id: 2 name: "mxtpu.decode.prefill" } }
  event_metadata { key: 3 value { id: 3 name: "mxtpu.decode.stage" } }
  event_metadata { key: 4 value { id: 4 name: "mxtpu.decode.sync" } }
  event_metadata { key: 5 value { id: 5 name: "mxtpu.decode.account" } }
  event_metadata { key: 6 value { id: 6 name: "PjitFunction(step)" } }
}
"""

HOST_ONLY = SYNTHETIC[SYNTHETIC.index('planes {\n  id: 3 name: "/host:CPU"'):]

DECODE = r"^jit_mxtpu_decode\("
PREFILL = r"^jit_mxtpu_prefill_b\d+\("


def _write(tmp_path, text, name):
    import jax
    path = tmp_path / name
    path.write_bytes(
        jax.profiler.ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


@pytest.fixture()
def synthetic(tmp_path):
    return named.load(_write(tmp_path, SYNTHETIC, "synthetic.xplane.pb"))


@pytest.fixture(scope="module")
def recorded():
    return named.load(RECORDED, "chipbench.")


# -- the recorded trace -------------------------------------------------------
# test_trace.py has its events by hand: three runs of jit_body, 3543, 3588
# and 3542 ns; busy 10,633 ns; chipbench_add_one 3 x 173 ns; window
# 46,685,435 -> 48,155,887.  Host spans on the line python3:
#   step_call [47,608,479 +384,100] [48,583,199 +214,980] [49,263,539 +184,040]
#   wait      [48,000,499 +574,860] [48,802,189 +456,610] [49,451,329 +477,010]
# Only the first step_call lies wholly inside the window, and it lies
# wholly inside the gap between the second and the third run.

def test_recorded_kernel_by_name(recorded):
    share = named.kernel_share(recorded, "chipbench_add_one")
    assert share == pytest.approx(100 * 519 / 10_633, rel=1e-2)
    # the one Mosaic kernel of the trace is all of its Pallas time
    assert share == pytest.approx(trace.reduce(RECORDED)["pallas_share"],
                                  rel=1e-9)
    assert named.kernel_share(recorded, "add_one") == share   # substring
    assert named.kernel_share(recorded, "mxtpu_rope") is None
    # a name that is there but is no Mosaic call does not count
    assert named.kernel_share(recorded, "convolution_tanh") is None


def test_recorded_module_by_pattern(recorded):
    body = r"^jit_body\("
    assert named.module_ms(recorded, body, 50) == pytest.approx(
        3543e-6, rel=1e-3)
    assert named.module_ms(recorded, body, 100) == pytest.approx(
        3588e-6, rel=1e-3)
    assert named.module_ms(recorded, DECODE, 50) is None
    assert named.module_ratio(recorded, body, body) == 1.0
    assert named.module_ratio(recorded, DECODE, body) == 0.0
    assert named.module_ratio(recorded, body, DECODE) is None


def test_recorded_spans_and_idle(recorded):
    assert [s[2:] for s in recorded["spans"]] == [
        ("chipbench.step_call", "python3"), ("chipbench.wait", "python3")] * 3
    assert named.span_ms(recorded, "step_call", 50) == pytest.approx(
        0.214980, rel=1e-4)
    assert named.span_ms(recorded, "wait", 0) == pytest.approx(
        0.456610, rel=1e-4)
    assert named.span_ms(recorded, "decode.step", 50) is None
    assert named.idle_in_span_ms(recorded, "step_call") == pytest.approx(
        0.384100, rel=1e-4)
    # no wait lies wholly inside the device's window
    assert named.idle_in_span_ms(recorded, "wait") is None
    gaps = named.idle_by_span(recorded)
    assert gaps["no_span"] == pytest.approx(779_830 * NS, rel=1e-3)
    assert gaps["chipbench.step_call"] == pytest.approx(679_972 * NS,
                                                        rel=1e-3)
    # the same gaps trace.reduce attributes, under the spans' full names
    assert {k.replace("chipbench.", ""): pytest.approx(v) for k, v
            in gaps.items()} == dict(trace.reduce(RECORDED)["idle_gaps"])


def test_recorded_table(recorded):
    t = named.table(recorded)
    assert t["devices"] == 1
    assert t["window_s"] == pytest.approx(1_470_452 * NS, rel=1e-5)
    assert t["kernels_s"] == {"chipbench_add_one": pytest.approx(519 * NS,
                                                                 rel=1e-2)}
    assert t["modules"] == {"jit_body": {
        "runs": 3, "ms_p50": pytest.approx(3543e-6, rel=1e-3)}}
    assert t["spans"]["chipbench.wait"]["count"] == 3
    assert list(t["idle_s_by_span"])[:2] == ["no_span",
                                             "chipbench.step_call"]
    json.dumps(t)


# -- the synthetic trace ------------------------------------------------------

def test_synthetic_kernels(synthetic, tmp_path):
    assert named.kernel_share(synthetic, "mxtpu_rope") == pytest.approx(
        100 * 20 / 174)
    assert named.kernel_share(synthetic, "mxtpu_paged_attention") == \
        pytest.approx(100 * 45 / 174)
    # the named kernels add up to what trace.reduce calls Pallas
    whole = trace.reduce(_write(tmp_path, SYNTHETIC, "again.xplane.pb"))
    assert whole["pallas_share"] == pytest.approx(100 * 65 / 174)
    assert named.kernel_share(synthetic, "mxtpu_") == pytest.approx(
        whole["pallas_share"])
    assert named.kernel_share(synthetic, "mxtpu_flash_fwd") is None


def test_synthetic_modules(synthetic):
    assert named.module_ms(synthetic, DECODE, 50) == pytest.approx(0.045)
    assert named.module_ms(synthetic, PREFILL, 50) == pytest.approx(0.015)
    assert named.module_ms(synthetic, r"^jit_mxtpu_prefill_b128\(", 50) == \
        pytest.approx(0.015)
    assert named.module_ratio(synthetic, PREFILL, DECODE) == 1.5
    # the pattern is anchored: a draft's executables are other programs
    assert named.module_runs(synthetic, r"^jit_mxtpu_draft") == []


def test_synthetic_spans(synthetic):
    assert named.span_ms(synthetic, "decode.step", 50) == \
        pytest.approx(0.0795)
    assert named.span_ms(synthetic, "decode.account", 50) == \
        pytest.approx(0.0035)
    # less what lies inside it on its own thread, not another thread's
    assert named.span_durations(synthetic, "decode.prefill") == \
        [pytest.approx(51 * US)]
    assert named.span_ms(synthetic, "decode.prefill", 50,
                         minus=["decode.stage", "decode.sync"]) == \
        pytest.approx(0.041)
    # a span outside the one asked about is not taken off it
    assert named.span_ms(synthetic, "decode.account", 100,
                         minus=["decode.stage"]) == pytest.approx(0.005)
    assert named.span_ms(synthetic, "step.dispatch", 50) is None


def test_synthetic_idle(synthetic):
    assert named.window(synthetic) == (0.0, pytest.approx(150 * US))
    assert named.idle_intervals(synthetic) == [
        (pytest.approx(40 * US), pytest.approx(50 * US)),
        (pytest.approx(95 * US), pytest.approx(100 * US))]
    assert named.idle_in_span_ms(synthetic, "decode.step") == \
        pytest.approx(0.013)
    assert named.idle_in_span_ms(synthetic, "decode.prefill") == \
        pytest.approx(0.006)          # [45,50) + [95,96)
    assert named.idle_in_span_ms(synthetic, "step.spmd") is None
    assert named.idle_by_span(synthetic) == {
        "mxtpu.decode.stage": pytest.approx(10 * US),
        "mxtpu.decode.account": pytest.approx(5 * US)}


def test_synthetic_table(synthetic):
    t = named.table(synthetic)
    assert t["devices"] == 2
    # seconds per device: summed over the planes, over their number
    assert t["kernels_s"] == {
        "mxtpu_paged_attention": pytest.approx(22.5 * US),
        "mxtpu_rope": pytest.approx(10 * US)}
    assert t["modules"]["jit_mxtpu_decode"] == {
        "runs": 2, "ms_p50": pytest.approx(0.045)}
    assert set(t["modules"]) == {"jit_mxtpu_decode",
                                 "jit_mxtpu_prefill_b128",
                                 "jit_mxtpu_prefill_b64"}
    assert t["spans"]["mxtpu.decode.stage"]["count"] == 2
    assert t["spans"]["mxtpu.decode.step"] == {
        "count": 2, "ms_p50": pytest.approx(0.0795)}


def test_no_device_plane_gives_nothing_but_the_spans(tmp_path):
    host = named.load(_write(tmp_path, HOST_ONLY, "host.xplane.pb"))
    assert host["devices"] == [] and len(host["spans"]) == 8
    assert named.kernel_share(host, "mxtpu_rope") is None
    assert named.module_ms(host, DECODE, 50) is None
    assert named.module_ratio(host, PREFILL, DECODE) is None
    assert named.idle_in_span_ms(host, "decode.step") is None
    assert named.idle_by_span(host) == {}
    assert named.span_ms(host, "decode.account", 50) == \
        pytest.approx(0.0035)
    t = named.table(host)
    assert t["devices"] == 0 and t["window_s"] is None
    assert t["kernels_s"] == {} and t["modules"] == {}
    empty = named.load(_write(tmp_path, 'planes { id: 3 name: "/host:CPU" }',
                              "empty.xplane.pb"))
    assert all(named.MODES[what](empty, **args) is None
               for what, args in [
                   ("kernel_share", {"match": "mxtpu_rope"}),
                   ("module_ms", {"pattern": DECODE, "q": 50}),
                   ("span_ms", {"name": "decode.step", "q": 50}),
                   ("idle_in_span_ms", {"name": "decode.step"})])


# -- the metric files, through the reader -------------------------------------

# per cell: metric -> what the synthetic trace gives it
DECODE_CELL = {
    "paged_attention_time_share": 100 * 45 / 174,
    "rope_time_share": 100 * 20 / 174,
    "decode_exec_ms_p50": 0.045,
    "prefill_exec_ms_p50": 0.015,
    "prefill_runs_per_step": 1.5,
    "sched_idle_ms_per_step": 0.013,
    "step_record_ms_p50": 0.0035,
}
TRAIN_CELLS = ["trainer_dispatch_ms_p50", "trainer_python_ms_p50"]
GPT2_TRAIN = ["flash_fwd_time_share", "flash_dkv_time_share",
              "flash_dq_time_share"]
NEW = sorted([*DECODE_CELL, *TRAIN_CELLS, *GPT2_TRAIN])

# what PR 24 wrote into each cell's per_layer list
PR24 = {
    "resnet50_train": ["trainer_host_ms_p50", "pallas_time_share",
                       "model_flops_util", "device_idle_share",
                       "peak_hbm_gb", "window_compiles"],
    "gpt2_train": ["trainer_host_ms_p50", "pallas_time_share",
                   "model_flops_util", "device_idle_share", "peak_hbm_gb",
                   "window_compiles"],
    "gpt2_decode_chat": ["gen_late_ms_p95", "request_ms_p95",
                         "token_gap_ms_p95", "first_answer_ms_p95",
                         "decode_step_ms_p50", "slots_active_mean",
                         "decode_pallas_time_share",
                         "serve_device_idle_share", "serve_peak_hbm_gb",
                         "window_compiles"],
}


def _spec(name):
    with open(os.path.join(BENCH_DIR, "layer_metrics", f"{name}.json")) as f:
        return json.load(f)


def _report():
    return load_module("tools", "named_report")


def test_the_tool_finds_the_twelve_metric_files():
    assert _report().metric_names() == NEW
    assert _report().metric_names("gpt2_decode_chat") == sorted(DECODE_CELL)
    assert _report().metric_names("gpt2_train") == sorted(
        TRAIN_CELLS + GPT2_TRAIN)
    assert _report().metric_names("resnet50_train") == sorted(TRAIN_CELLS)


@pytest.mark.parametrize("name", NEW)
def test_metric_file_passes_the_contracts_rules(name):
    """What test_contract.py holds a metric of BENCHMARK.json to, held
    here for the files no cell lists yet."""
    with open(os.path.join(REPO_DIR, "BENCHMARK.json")) as f:
        bench = json.load(f)
    spec = _spec(name)
    assert spec["name"] == name and spec["reader"] == "trace_named"
    assert re.match(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$", name)
    assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", spec["unit"])
    assert spec["better"] == "lower"
    assert spec["source"] in ("device_trace", "program_span")
    assert spec["layer"] in {m["layer"] for m in bench["per_layer"]}
    assert spec["args"]["what"] in named.MODES
    cells = {w["name"] for w in bench["workloads"]}
    assert spec["workloads"] and set(spec["workloads"]) <= cells
    # reported only where the metric it moves is
    moved, = [m for m in bench["end_to_end"] if m["name"] == spec["moves"]]
    assert set(spec["workloads"]) <= set(moved["workloads"])


def test_each_cell_lists_what_pr24_wrote_then_new_names_only():
    for cell, was in PR24.items():
        with open(os.path.join(BENCH_DIR, "workloads", f"{cell}.json")) as f:
            now = json.load(f)["per_layer"]
        assert now[:len(was)] == was, cell
        added = now[len(was):]
        assert len(added) == len(set(added))
        assert set(added) <= set(_report().metric_names(cell)), cell


def test_metrics_through_the_reader(synthetic):
    obs = {"_named": synthetic}
    got = layer_metrics(obs, NEW)
    assert {k: v["value"] for k, v in got.items()} == {
        k: pytest.approx(v) for k, v in DECODE_CELL.items()}
    assert got["rope_time_share"]["unit"] == "%"
    assert got["prefill_runs_per_step"]["unit"] == "count"
    # the two Pallas shares are the decode cell's whole Pallas share
    assert got["rope_time_share"]["value"] \
        + got["paged_attention_time_share"]["value"] == \
        pytest.approx(100 * 65 / 174)


def test_reader_takes_this_runs_trace_and_no_older(tmp_path, monkeypatch,
                                                   capsys):
    reader = load_module("readers", "trace_named")
    monkeypatch.setattr(reader, "OUT_DIR", str(tmp_path))
    run_dir = tmp_path / "trace_some_cell" / "plugins" / "profile" / "run1"
    run_dir.mkdir(parents=True)
    _write(run_dir, SYNTHETIC, "host.xplane.pb")
    args = {"what": "module_ratio", "pattern": PREFILL, "per": DECODE}
    # the window opened after the file was written: an earlier run's trace
    stale = {"cell": {"name": "some_cell"},
             "t_window_start": time.monotonic() + 5.0}
    assert reader.read(stale, **args) is None
    assert reader.read(stale, what="span_ms", name="decode.step",
                       q=50) is None
    assert capsys.readouterr().out == ""
    fresh = {"cell": {"name": "some_cell"},
             "t_window_start": time.monotonic() - 5.0}
    assert reader.read(fresh, **args) == 1.5
    assert reader.read(fresh, what="kernel_share",
                       match="mxtpu_rope") == pytest.approx(100 * 20 / 174)
    said = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert len(said) == 1                   # parsed once, said once
    assert said[0]["chipbench"] == "named"
    assert "mxtpu_paged_attention" in said[0]["kernels_s"]
    assert "jit_mxtpu_prefill_b128" in said[0]["modules"]
    assert "mxtpu.decode.account" in said[0]["spans"]
    # no trace at all
    none = {"cell": {"name": "no_such_cell"},
            "t_window_start": time.monotonic()}
    assert reader.read(none, **args) is None


# -- rehearsals: the program's spans reach a capture on the CPU --------------

def _run(*argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO_DIR)
    env.pop("XLA_FLAGS", None)
    env.pop("MXNET_TRACE", None)
    proc = subprocess.run([sys.executable, *argv], env=env, cwd=REPO_DIR,
                          capture_output=True, text=True, timeout=900)
    return proc, [json.loads(ln) for ln in proc.stdout.splitlines()
                  if ln.startswith("{")]


@pytest.mark.parametrize("cell", sorted(PR24))
def test_rehearsal_still_prints_its_line_and_its_trace_holds_the_spans(cell):
    proc, lines = _run(os.path.join(BENCH_DIR, "run.py"), "--workload", cell,
                       "--seed", "3", "--seconds", "2", "--trace", "1",
                       "--rehearse")
    assert proc.returncode == 1, proc.stderr[-2000:]
    assert set(lines[-1]) == {"correct", "attempted", "failed", "metrics",
                              "device"}
    with open(os.path.join(BENCH_DIR, "workloads", f"{cell}.json")) as f:
        assert set(lines[-1]["metrics"]) <= set(json.load(f)["per_layer"])
    proc, lines = _run(
        os.path.join(BENCH_DIR, "tools", "named_report.py"),
        os.path.join(BENCH_DIR, "out", f"trace_{cell}"), "--workload", cell)
    assert proc.returncode == 0, proc.stderr[-2000:]
    table, metrics = lines
    assert table["devices"] == 0 and table["kernels_s"] == {}
    got = metrics["metrics"]
    if cell == "gpt2_decode_chat":
        assert {"mxtpu.decode." + n for n in (
            "step", "expire", "admit_phase", "prefill", "decode", "account",
            "stage", "sync")} <= set(table["spans"])
        assert set(got) == {"step_record_ms_p50"}
        assert 0 < got["step_record_ms_p50"]["value"] < \
            table["spans"]["mxtpu.decode.step"]["ms_p50"]
    else:
        assert {"mxtpu.step.spmd", "mxtpu.step.dispatch"} <= \
            set(table["spans"])
        assert set(got) == set(TRAIN_CELLS)
        assert all(0 < m["value"] for m in got.values())
