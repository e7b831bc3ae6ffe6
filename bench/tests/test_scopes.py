"""The program's spans and scopes as the benchmark reads them (``scopes``):
``op_name`` classification, a made-up compiled module and trace, a trace of
the program without spans or scopes (``small_train``, which must read as
nothing), and a small trace of the program with them, recorded on the chip
(``small_scoped``)."""
import dataclasses
import gzip
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

import devtrace
import scopes
from devtrace import Op

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"


@pytest.mark.parametrize("op_name,want", [
    ("jit(train_step)/while/body/closed_call/jvp(layer01)/conv/conv_general_dilated",
     ("layer01", "conv", "fwd")),
    ("jit(train_step)/while/body/closed_call/transpose(jvp(layer01))/pool/select_and_scatter",
     ("layer01", "pool", "bwd")),
    ("jit(train_step)/while/body/closed_call/transpose(jvp(layer16))/bn/mul",
     ("layer16", "bn", "bwd")),
    ("jit(train_step)/jvp(layer03)/halo/ppermute", ("layer03", "halo", "fwd")),
    ("jit(train_step)/while/body/closed_call/transpose(jvp(loss))/mul", (None, "loss", "bwd")),
    ("jit(train_step)/grad_sum/psum", (None, "grad_sum", "fwd")),
    ("jit(train_step)/optimizer/mul", (None, "optimizer", "fwd")),
    # no scope: the program before it named its work, and XLA's own ops
    ("jit(train_step)/while/body/closed_call/transpose(jvp())/select_and_scatter",
     (None, "unscoped", "bwd")),
    ("jit(train_step)/while/body/closed_call/jvp(jit(_where))/select_n", (None, "unscoped", "fwd")),
    ("", (None, "unscoped", "fwd")),
    # a primitive is never read as a scope
    ("jit(train_step)/jvp(layer02)/reshard", ("layer02", "unscoped", "fwd")),
])
def test_classify_an_op_name(op_name, want):
    assert scopes.classify(op_name) == want


HLO = """HloModule jit_step

%fused_computation.1 (p0: f32[2,4,4,3], p1: f32[3,3,3,8]) -> f32[2,4,4,8] {
  %p0 = f32[2,4,4,3]{3,2,1,0} parameter(0)
  %p1 = f32[3,3,3,8]{3,2,1,0} parameter(1)
  ROOT %convolution.1 = f32[2,4,4,8]{3,2,1,0} convolution(f32[2,4,4,3]{3,2,1,0} %p0, f32[3,3,3,8]{3,2,1,0} %p1), window={size=3x3 pad=1_1x1_1}, dim_labels=b01f_01io->b01f, metadata={op_name="jit(step)/jvp(layer01)/conv/conv_general_dilated"}
}

ENTRY %main.9 (Arg_0.1: f32[2,4,4,3], Arg_1.2: f32[3,3,3,8]) -> f32[2,4,4,8] {
  %Arg_0.1 = f32[2,4,4,3]{3,2,1,0} parameter(0)
  %Arg_1.2 = f32[3,3,3,8]{3,2,1,0} parameter(1)
  %fusion.5 = f32[2,4,4,8]{3,2,1,0:T(8,128)} fusion(f32[2,4,4,3]{3,2,1,0} %Arg_0.1, f32[3,3,3,8]{3,2,1,0} %Arg_1.2), kind=kOutput, calls=%fused_computation.1
  %copy.6 = f32[2,4,4,8]{0,1,2,3} copy(%fusion.5)
  %multiply.7 = f32[2,4,4,8]{3,2,1,0} multiply(%copy.6, %copy.6), metadata={op_name="jit(step)/transpose(jvp(layer01))/bn/mul"}
  ROOT %add.8 = f32[2,4,4,8]{3,2,1,0} add(%multiply.7, %Arg_0.1)
}
"""


def test_instructions_take_a_scope_from_their_root_or_operand():
    got = scopes.instruction_scopes(HLO)
    assert got["convolution.1"] == ("layer01", "conv", "fwd")
    assert got["fusion.5"] == ("layer01", "conv", "fwd")          # its root's
    assert got["copy.6"] == ("layer01", "conv", "fwd")            # its operand's
    assert got["multiply.7"] == ("layer01", "bn", "bwd")
    assert got["add.8"] == ("layer01", "bn", "bwd")               # first operand
    assert got["Arg_0.1"] == (None, "unscoped", "fwd")


def made_up_run():
    """Two chips, a 100 ns window of two steps; the host waits in
    ``driver.wait`` over [40, 60] and [80, 95]."""
    chip = [Op("fusion.5", 10, 50, "fusion"), Op("multiply.7", 50, 58, "multiply"),
            Op("add.8", 75, 100, "add")]
    devices = {0: chip, 1: [dataclasses.replace(o) for o in chip]}
    bench = {devtrace.SOURCE_SPAN: [(0, 7), (70, 72), (100, 105)]}
    summary = devtrace.reduce(devices, bench, chips=2, steps=2)
    busy = {i: devtrace.union((o.start, o.end) for o in ops) for i, ops in devices.items()}
    spans = {scopes.WAIT: [(40, 60), (80, 95)], "driver.make_batch": [(0, 8), (69, 73)]}
    run = SimpleNamespace(trace=summary, result={"hlo": HLO, "window": {"steps": 2}})
    return run, busy, spans


def test_scope_ms_and_idle_split_on_made_up_ops():
    run, busy, spans = made_up_run()
    assert scopes.scope_ms(run, "conv") == pytest.approx(1e3 * 40e-9 / 2)
    assert scopes.scope_ms(run, "bn") == pytest.approx(1e3 * 33e-9 / 2)
    assert scopes.scope_ms(run, "pool") is None
    split = scopes.idle_split(busy, spans, run.trace.window, chips=2)
    # idle [0, 10] and [58, 75]: the host waited over [58, 60]
    assert split["wait"] == pytest.approx(2e-9)
    assert split["host"] == pytest.approx(25e-9)
    assert split["wait"] + split["host"] == pytest.approx(run.trace.window_s - run.trace.busy_s)
    assert split["in"]["driver.make_batch"] == pytest.approx(8e-9 + 4e-9)


def layer_run(name):
    """A ``run_cell.LayerRun``-like view of a recorded trace."""
    devices, bench = devtrace.read(str(TESTDATA / f"{name}.xplane.pb.gz"))
    expect = json.loads((TESTDATA / f"{name}.expect.json").read_text())
    summary = devtrace.reduce(devices, bench, chips=1, steps=expect["steps"])
    hlo = gzip.open(TESTDATA / f"{name}.hlo.txt.gz", "rt").read()
    run = SimpleNamespace(trace=summary, result={"hlo": hlo, "window": {"steps": expect["steps"]}})
    busy, spans = scopes.read_trace(str(TESTDATA / f"{name}.xplane.pb.gz"))
    return run, busy, spans, expect


def test_a_program_without_spans_or_scopes_reads_as_nothing():
    run, busy, spans, _ = layer_run("small_train")
    assert scopes.seconds_by_scope(run) is None
    assert all(scopes.scope_ms(run, s) is None for s in scopes.SCOPES)
    assert spans == {}
    assert scopes.idle_split(busy, spans, run.trace.window, chips=1) is None


def test_a_scoped_trace_recorded_on_the_chip(tmp_path):
    """A two-step window of YOLOv2-16 at 64x64, batch 4, recorded on a TPU v5e
    chip with the program's spans and scopes; ``small_scoped.expect.json``
    holds what the metrics read on the chip."""
    import importlib

    run, busy, spans, expect = layer_run("small_scoped")
    by = scopes.seconds_by_scope(run)
    # every op's time lands in one (layer, scope, direction), unscoped included
    assert sum(by.values()) == pytest.approx(sum(run.trace.op_s.values()), rel=1e-12)
    assert {s for _, s, _ in by} >= {"conv", "bn", "pool", "halo", "loss", "optimizer"}
    assert {d for _, _, d in by} == {"fwd", "bwd"}
    # unscoped: the batch's input copy and XLA's prefetches of state leaves,
    # 6.7 % of busy time at this size (0.46 % at 416x416, batch 64)
    unscoped = sum(v for (_, s, _), v in by.items() if s == scopes.UNSCOPED)
    assert unscoped < 0.1 * run.trace.busy_s
    split = scopes.idle_split(busy, spans, run.trace.window, chips=1)
    assert split["wait"] + split["host"] == pytest.approx(run.trace.window_s - run.trace.busy_s,
                                                          rel=1e-9)
    assert split["in"]["arch.place_batch"] > 0
    # the readers, as a traced run calls them, give what they gave on the chip
    trace_dir = tmp_path / "plugins" / "profile"
    trace_dir.mkdir(parents=True)
    with gzip.open(TESTDATA / "small_scoped.xplane.pb.gz", "rb") as f:
        (trace_dir / "t.xplane.pb").write_bytes(f.read())
    ctx = SimpleNamespace(trace_dir=str(tmp_path), devices=[None])
    layer = SimpleNamespace(ctx=ctx, trace=run.trace, result=run.result)
    got = {name: importlib.import_module(f"layer_metrics.{name}").read(layer)
           for name in expect["metrics"]}
    assert got == pytest.approx(expect["metrics"], rel=1e-9)
    assert got["idle_wait_share"] + got["idle_host_share"] == pytest.approx(
        got["device_idle_share"], rel=1e-9)
