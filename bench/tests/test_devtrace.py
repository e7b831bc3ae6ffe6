"""The trace reduction: interval arithmetic on made-up ops, and the whole
reduction on a small trace recorded on the chip (``bench/testdata``)."""
import dataclasses
from pathlib import Path

import pytest

import devtrace
from devtrace import Op

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"


def test_union_intersect_complement():
    u = devtrace.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert u == [(0, 3), (5, 8)]
    assert devtrace.length(u) == 6
    assert devtrace.intersect(u, [(2, 6)]) == [(2, 3), (5, 6)]
    assert devtrace.complement(u, -1, 10) == [(-1, 0), (3, 5), (8, 10)]
    assert devtrace.clip(u, 1, 6) == [(1, 3), (5, 6)]


def made_up():
    """Two chips over a 100 ns window: a compute op, a collective half hidden
    behind it, and a gap while the host was in the batch source."""
    spans = {devtrace.SOURCE_SPAN: [(0, 7), (70, 72), (100, 105)],
             devtrace.STEP_SPAN: [(7, 9), (72, 74)]}
    chip = [Op("fusion.1", 10, 50, "fusion", "f32[4]"),
            Op("all-reduce-start.2", 40, 58, "all-reduce-start"),
            Op("fusion.3", 75, 100, "fusion")]
    return {0: chip, 1: [dataclasses.replace(o) for o in chip]}, spans


def test_reduce_made_up():
    devices, spans = made_up()
    s = devtrace.reduce(devices, spans, chips=2, steps=2)
    assert s.window == (0, 100)
    assert s.busy_s == pytest.approx(73e-9)
    assert s.collective_s == pytest.approx(18e-9)
    assert s.collective_exposed_s == pytest.approx(8e-9)
    assert s.op_s["fusion.1"] == pytest.approx(40e-9)
    # [0, 10] mostly in the batch source; [58, 75] mostly in neither span
    assert sorted(s.gaps) == [(devtrace.SOURCE_SPAN, pytest.approx(10e-9))] * 2 + [
        (devtrace.OTHER_HOST, pytest.approx(17e-9))] * 2
    b = s.breakdown()
    assert b["device_ops"][0] == ["fusion.1 fusion f32[4]", pytest.approx(40e-9)]
    assert len(b["idle_gaps"]) == 4


def test_reduce_needs_the_window_and_the_chips():
    devices, spans = made_up()
    with pytest.raises(ValueError):
        devtrace.reduce(devices, spans, chips=2, steps=3)
    with pytest.raises(ValueError):
        devtrace.reduce(devices, spans, chips=4, steps=2)


HLO = """HloModule jit_step

%fused_computation.1 (p0: f32[2,4,4,3], p1: f32[3,3,3,8]) -> f32[2,4,4,8] {
  %p0 = f32[2,4,4,3]{3,2,1,0} parameter(0)
  %p1 = f32[3,3,3,8]{3,2,1,0} parameter(1)
  ROOT %convolution.1 = f32[2,4,4,8]{3,2,1,0} convolution(f32[2,4,4,3]{3,2,1,0} %p0, f32[3,3,3,8]{3,2,1,0} %p1), window={size=3x3 pad=1_1x1_1}, dim_labels=b01f_01io->b01f
}

%fused_computation.2 (p0: f32[2,4,4,8]) -> f32[2,4,4,8] {
  %p0 = f32[2,4,4,8]{3,2,1,0} parameter(0)
  ROOT %multiply.3 = f32[2,4,4,8]{3,2,1,0} multiply(f32[2,4,4,8]{3,2,1,0} %p0, f32[2,4,4,8]{3,2,1,0} %p0)
}

ENTRY %main.9 (Arg_0.1: f32[2,4,4,3], Arg_1.2: f32[3,3,3,8]) -> (f32[2,4,4,8], f32[8]) {
  %Arg_0.1 = f32[2,4,4,3]{3,2,1,0} parameter(0)
  %Arg_1.2 = f32[3,3,3,8]{3,2,1,0} parameter(1)
  %fusion.5 = f32[2,4,4,8]{3,2,1,0:T(8,128)} fusion(f32[2,4,4,3]{3,2,1,0} %Arg_0.1, f32[3,3,3,8]{3,2,1,0} %Arg_1.2), kind=kOutput, calls=%fused_computation.1
  %fusion.6 = f32[2,4,4,8]{3,2,1,0} fusion(f32[2,4,4,8]{3,2,1,0} %fusion.5), kind=kLoop, calls=%fused_computation.2
  %custom-call.7 = f32[8]{0} custom-call(f32[2,4,4,8]{3,2,1,0} %fusion.6), custom_call_target="tpu_custom_call"
  %custom-call.8 = f32[8]{0} custom-call(f32[2,4,4,8]{3,2,1,0} %fusion.6), custom_call_target="AllocateBuffer"
  ROOT %tuple.9 = (f32[2,4,4,8]{3,2,1,0}, f32[8]{0}) tuple(f32[2,4,4,8]{3,2,1,0} %fusion.6, f32[8]{0} %custom-call.7)
}
"""


def test_conv_ops_from_the_compiled_module():
    assert devtrace.conv_ops(HLO) == {"convolution.1", "fusion.5", "custom-call.7"}


def test_parse_a_trace_event_name():
    text = ("%fusion.150 = (f32[]{:T(128)}, f32[3,3,3,32]{3,2,1,0:T(4,128)S(1)}) "
            "fusion(f32[64,416,416,32]{0,3,2,1:T(8,128)} %get-tuple-element.413), "
            "kind=kOutput, calls=%fused_computation.355")
    name, shape, opcode, rest = devtrace.parse_instr(text)
    assert (name, opcode) == ("fusion.150", "fusion")
    assert shape.startswith("(f32[]") and shape.endswith("S(1)})")
    assert "calls=%fused_computation.355" in rest
    assert devtrace.is_collective(devtrace.parse_instr(
        "%collective-permute-done.1 = bf16[8,1,128,3]{0} collective-permute-done(%x)")[2])


def test_reduce_a_trace_recorded_on_the_chip():
    """A two-step window of YOLOv2-16 at 64x64, batch 4, recorded on a TPU v5e
    chip, with the compiled module that ran; ``small_train.expect.json`` holds
    what the reduction gave on the chip."""
    import gzip
    import json

    expect = json.loads((TESTDATA / "small_train.expect.json").read_text())
    devices, spans = devtrace.read(str(TESTDATA / "small_train.xplane.pb.gz"))
    assert len(spans[devtrace.SOURCE_SPAN]) == expect["steps"] + 1
    s = devtrace.reduce(devices, spans, chips=1, steps=expect["steps"])
    hlo = gzip.open(TESTDATA / "small_train.hlo.txt.gz", "rt").read()
    conv = s.seconds_of(devtrace.conv_ops(hlo))
    for key, value in (("busy_s", s.busy_s), ("window_s", s.window_s), ("conv_s", conv),
                       ("collective_s", s.collective_s)):
        assert value == pytest.approx(expect[key], rel=1e-9), key
    assert 0 < conv < s.busy_s < s.window_s
    assert sum(s.op_s.values()) >= s.busy_s * (1 - 1e-9)
    assert sum(g for _, g in s.gaps) == pytest.approx(s.window_s - s.busy_s, rel=1e-9)
    assert s.breakdown() == json.loads(json.dumps(expect["breakdown"]))
