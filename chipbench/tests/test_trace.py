"""The trace reduction against traces whose numbers were worked out by
hand: a synthetic one written as a text proto below, and a small one
recorded on a TPU v5e kept beside this file."""
import os

import pytest

from chipbench.harness import trace

HERE = os.path.dirname(os.path.abspath(__file__))

# Two devices, times in microseconds from the session's start.
#
# TPU:0   fusion.1        [  0, 40)   compute
#         while.9         [  0, 100)  container: left out
#         flash.2         [ 50, 60)   Pallas: custom call, tpu_custom_call
#         custom-call.7   [ 10, 12)   another custom call: plain compute
#         all-reduce.3    [ 55, 85)   collective, [55, 60) under the kernel
#         fusion.4        [ 80, 100)  compute, [80, 85) under the collective
#   busy   = [0,40) + [50,100)               = 90 of a 100 us window
#   pallas = 10, collective = 30, exposed = [60, 80) = 20
#   idle   = [40, 50): host span chipbench.step_call covers its middle
#
# TPU:1   fusion.1        [  0, 30)
#         all-gather-start.5 [30, 40), all-gather-done.5 [70, 80)
#         fusion.6        [ 35, 75)
#   busy   = [0, 80) = 80; collective = 20; exposed = [30,35) + [75,80) = 10
#
# mean busy = 85 us; idle share = 15%; pallas share = (10 + 0)/2 / 85;
# collective share = (30 + 20)/2 / 85; exposed = (20 + 10)/2 / 85
SYNTHETIC = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 40000000 }
    events { metadata_id: 9 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 50000000 duration_ps: 10000000 }
    events { metadata_id: 6 offset_ps: 10000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 55000000 duration_ps: 30000000 }
    events { metadata_id: 4 offset_ps: 80000000 duration_ps: 20000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 7 offset_ps: 0 duration_ps: 100000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "%flash.2 = bf16[8]{0:T(8)(2,1)} custom-call(bf16[8]{0} %p.1), custom_call_target=\\"tpu_custom_call\\", frontend_attributes={}" } }
  event_metadata { key: 6 value { id: 6 name: "%custom-call.7 = f32[8]{0:T(8)S(1)} custom-call(f32[4]{0} %a, f32[4]{0} %b), custom_call_target=\\"ConcatBitcast\\"" } }
  event_metadata { key: 3 value { id: 3 name: "%all-reduce.3 = f32[8]{0:T(8)} all-reduce(f32[8]{0} %x), replica_groups={{0,1}}, to_apply=%add" } }
  event_metadata { key: 4 value { id: 4 name: "fusion.4" } }
  event_metadata { key: 7 value { id: 7 name: "jit_step(123)" } }
  event_metadata { key: 9 value { id: 9 name: "while.9" } }
}
planes {
  id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 30000000 }
    events { metadata_id: 5 offset_ps: 30000000 duration_ps: 10000000 }
    events { metadata_id: 6 offset_ps: 35000000 duration_ps: 40000000 }
    events { metadata_id: 8 offset_ps: 70000000 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 5 value { id: 5 name: "all-gather-start.5" } }
  event_metadata { key: 6 value { id: 6 name: "fusion.6" } }
  event_metadata { key: 8 value { id: 8 name: "all-gather-done.5" } }
}
planes {
  id: 3 name: "/host:CPU"
  lines { id: 1 name: "main" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 38000000 }
    events { metadata_id: 2 offset_ps: 42000000 duration_ps: 6000000 }
    events { metadata_id: 3 offset_ps: 41000000 duration_ps: 9000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "chipbench.wait" } }
  event_metadata { key: 2 value { id: 2 name: "chipbench.step_call" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(step)" } }
}
"""


def test_interval_arithmetic():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == \
        [(0, 3), (5, 8)]
    assert trace.length([(0, 3), (5, 8)]) == 6
    assert trace.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert trace.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert trace.subtract([(0, 4)], []) == [(0, 4)]
    assert trace.subtract([(1, 2)], [(0, 5)]) == []


def test_names():
    assert trace.opcode("all-reduce-start.3") == "all-reduce-start"
    assert trace.opcode("%fusion.12 = bf16[4] fusion(...)") == "fusion"
    assert trace.is_collective("all-reduce.1")
    assert trace.is_collective("all-gather-start.5")
    assert trace.is_collective("reduce-scatter-done")
    assert not trace.is_collective("fusion.3")
    assert not trace.is_collective("all-reduce-scatter-fusion")
    assert trace.is_container("while.9") and not trace.is_container("fusion")
    mosaic = ('%jvp_jit__unknown___.6 = (bf16[64,1024,64]{2,1,0:T(8,128)(2,1)'
              'S(1)}, f32[64,1024,128]{2,1,0:T(8,128)S(1)}) custom-call(bf16[64,'
              '1024,64]{2,1,0:T(8,128)(2,1)S(1)} %bitcast.3004), '
              'custom_call_target="tpu_custom_call", frontend_attributes={}')
    other = ('%custom-call.467 = f32[1024,1024]{1,0:T(8,128)S(1)} custom-call('
             'f32[256,1024]{1,0:T(8,128)S(1)} %slice-done.1852), '
             'custom_call_target="ConcatBitcast"')
    assert trace.opcode(mosaic) == trace.opcode(other) == "custom-call"
    assert trace.is_pallas(mosaic) and not trace.is_pallas(other)
    # a custom call that does not say its target cannot be told apart
    assert not trace.is_pallas("custom-call.2")
    assert trace.label(mosaic) == \
        "jvp_jit__unknown___.6 custom-call:tpu_custom_call"
    assert trace.instruction(other) == "custom-call.467"
    fused = ('%fusion.1881 = (bf16[256]{0:T(256)(128)(2,1)}, /*index=5*/bf16['
             '256,256,56,56]{1,0,3,2:T(8,128)(2,1)}) fusion(bf16[256]{0:T(256)'
             '(128)(2,1)S(1)} %copy-done.422), kind=kOutput, calls=%fused.2617')
    assert trace.opcode(fused) == "fusion"
    assert trace.label(fused) == "fusion.1881 fusion"
    assert trace.is_collective(
        "%all-reduce-start.3 = f32[8]{0} all-reduce-start(f32[8]{0} %x)")


@pytest.fixture()
def synthetic(tmp_path):
    import jax
    raw = jax.profiler.ProfileData.text_proto_to_serialized_xspace(SYNTHETIC)
    path = tmp_path / "synthetic.xplane.pb"
    path.write_bytes(raw)
    return str(path)


def test_reduction_of_the_synthetic_trace(synthetic):
    s = trace.reduce(synthetic)
    us = 1e-6
    assert s["devices"] == 2
    assert s["window_s"] == pytest.approx(100 * us)
    assert s["busy_s"] == pytest.approx(85 * us)
    assert s["idle_share"] == pytest.approx(15.0)
    assert s["pallas_share"] == pytest.approx(100 * 5 / 85)
    assert s["collective_share"] == pytest.approx(100 * 25 / 85)
    assert s["collective_exposed_share"] == pytest.approx(100 * 15 / 85)
    ops = dict(s["device_ops"])
    assert not any(k.startswith("while") for k in ops)
    assert ops["flash.2 custom-call:tpu_custom_call"] == \
        pytest.approx(5 * us)
    # seconds per device: fusion.1 ran 40 us on one and 30 us on the other
    assert ops["fusion.1 fusion"] == pytest.approx(35 * us)
    assert ops["fusion.6 fusion"] == pytest.approx(20 * us)
    assert s["device_ops"][0][0] == "fusion.1 fusion"
    # the idle gap [40, 50) of the first device lies under step_call
    assert s["idle_gaps"] == [["step_call", pytest.approx(10 * us)]]


def test_a_trace_with_no_device_operation_gives_nothing(tmp_path):
    import jax
    raw = jax.profiler.ProfileData.text_proto_to_serialized_xspace(
        'planes { id: 3 name: "/host:CPU" }')
    path = tmp_path / "host_only.xplane.pb"
    path.write_bytes(raw)
    assert trace.reduce(str(path)) is None


# Recorded on one TPU v5e chip by chipbench/tools/record_fixture.py (my
# chip run, PR 24): three runs of one program, each five operations on
# the XLA Ops line, back to back (times in ns, read off the file by hand
# with chipbench/tools/dump_trace.py):
#
#   copy-start 13 | copy-done 2 | convolution_tanh_fusion 1503 |
#   chipbench_add_one.1 (custom call, tpu_custom_call) 173 |
#   broadcast_multiply_fusion 1838        first run:  3529 ns busy
#   13 | 2 | 1502 | 173 | 1884            second run: 3574 ns
#   14 | 2 | 1503 | 173 | 1838            third run:  3530 ns
#
# window = 46,685,435 -> 48,155,887 = 1,470,452 ns; busy = 10,633 ns;
# Pallas = 3 x 173 = 519 ns.  The two long gaps are 779,830 ns and
# 679,972 ns.  The device's clock runs about 1 ms ahead of the host's in
# this file (each program starts "before" the step_call that sent it),
# so the first long gap falls before any span and the second under the
# first step_call.
RECORDED = os.path.join(HERE, "data", "v5e_1chip.xplane.pb")


def test_reduction_of_the_recorded_trace():
    s = trace.reduce(RECORDED)
    ns = 1e-9
    assert s["devices"] == 1
    assert s["window_s"] == pytest.approx(1_470_452 * ns, rel=1e-5)
    assert s["busy_s"] == pytest.approx(10_633 * ns, rel=1e-3)
    assert s["idle_share"] == pytest.approx(
        100 * (1 - 10_633 / 1_470_452), rel=1e-5)
    assert s["pallas_share"] == pytest.approx(100 * 519 / 10_633, rel=1e-2)
    assert s["collective_share"] == 0.0
    ops = dict(s["device_ops"])
    assert ops["chipbench_add_one.1 custom-call:tpu_custom_call"] == \
        pytest.approx(519 * ns, rel=1e-2)
    assert ops["broadcast_multiply_fusion fusion"] == \
        pytest.approx((1838 + 1884 + 1838) * ns, rel=1e-3)
    assert ops["convolution_tanh_fusion fusion"] == \
        pytest.approx((1503 + 1502 + 1503) * ns, rel=1e-3)
    gaps = dict(s["idle_gaps"])
    assert gaps["no_span"] == pytest.approx(779_830 * ns, rel=1e-3)
    assert gaps["step_call"] == pytest.approx(679_972 * ns, rel=1e-3)
    assert gaps["between_ops"] < 30 * ns
    assert [g[0] for g in s["longest_gaps"][:2]] == ["no_span", "step_call"]


# Recorded on a four-chip TPU v5e host by the same tool (my chip run,
# PR 24): three rounds of matmul, the Pallas kernel, an all-reduce over
# the four chips (``%psum.7 = ... all-reduce(...)``) and a multiply, 18
# events on each device's XLA Ops line.  The first round on TPU:0, in ns:
# copy-start 2 | copy-done 3 | fusion 1502 | chipbench_add_one.1 173 |
# psum.7 22,133 | fusion 1837.  Summed by hand over the three rounds:
#
#   device   busy     all-reduce   Pallas
#   TPU:0    77,066   66,520       520
#   TPU:1    74,997   64,409       518
#   TPU:2    74,889   64,346       518
#   TPU:3    71,489   60,948       518
#   mean     74,610.25  64,055.75  518.5
#
# Nothing else runs on a chip while it waits in the all-reduce, so all of
# it is exposed.  Window: 147,118,811 (TPU:0's first) to 149,281,840
# (TPU:1's last) = 2,163,029 ns.
RECORDED_4 = os.path.join(HERE, "data", "v5e_4chip.xplane.pb")


def test_reduction_of_the_recorded_four_chip_trace():
    s = trace.reduce(RECORDED_4)
    ns = 1e-9
    assert s["devices"] == 4
    assert s["window_s"] == pytest.approx(2_163_029 * ns, rel=1e-5)
    assert s["busy_s"] == pytest.approx(74_610.25 * ns, rel=1e-4)
    assert s["collective_share"] == pytest.approx(
        100 * 64_055.75 / 74_610.25, rel=1e-4)
    assert s["collective_exposed_share"] == pytest.approx(
        s["collective_share"], rel=1e-6)
    assert s["pallas_share"] == pytest.approx(100 * 518.5 / 74_610.25,
                                              rel=1e-2)
    assert s["idle_share"] == pytest.approx(
        100 * (1 - 74_610.25 / 2_163_029), rel=1e-5)
    assert s["device_ops"][0][0] == "psum.7 all-reduce"
    assert s["device_ops"][0][1] == pytest.approx(64_055.75 * ns, rel=1e-4)
