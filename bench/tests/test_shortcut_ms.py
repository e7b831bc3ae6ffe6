"""The ``shortcut_ms`` reader on a small residual train step recorded on the
CPU (``small_residual``: YOLOv3's layers 0-11 at 32x32, batch 4, 1x1 grid,
two steps under ``jax.profiler``; its compiled module beside it).

The CPU has no device plane: its trace names each instruction it ran on
the host's XLA threads, which stand in for the chip's ``XLA Ops`` line
here. ``small_residual.expect.json`` holds what the reader read."""
import collections
import gzip
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

import devtrace
import scopes
from layer_metrics import shortcut_ms

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"


def cpu_run(name):
    """A ``run_cell.LayerRun``-like view of a CPU recording: each
    instruction's seconds, summed over the host threads that ran it."""
    hlo = gzip.open(TESTDATA / f"{name}.hlo.txt.gz", "rt").read()
    known = set(scopes.instruction_scopes(hlo))
    op_s = collections.defaultdict(float)
    for plane in scopes._load(str(TESTDATA / f"{name}.xplane.pb.gz")).planes:
        for line in plane.lines:
            if plane.name.startswith("/host:") and line.name.startswith("tf_XLA"):
                for e in line.events:
                    if e.name in known:
                        op_s[e.name] += e.duration_ns * 1e-9
    expect = json.loads((TESTDATA / f"{name}.expect.json").read_text())
    trace = devtrace.Summary(chips=1, window=(0, 0), busy_s=sum(op_s.values()), window_s=0.0,
                             op_s=dict(op_s), op_kind={}, collective_s=0.0,
                             collective_exposed_s=0.0, gaps=[])
    return SimpleNamespace(trace=trace, result={"hlo": hlo, "window": {"steps": expect["steps"]}}), expect


def test_the_adds_are_under_shortcut():
    run, expect = cpu_run("small_residual")
    names = shortcut_ms.shortcut_instructions(run.result["hlo"])
    with_scope = scopes.instruction_scopes(run.result["hlo"])
    # the three shortcuts of layers 0-11 (plan layers 4, 8, 11)
    assert {with_scope[n][0] for n in names} == set(expect["shortcut_layers"])
    assert all(with_scope[n][1] == scopes.UNSCOPED for n in names)   # unknown to SCOPES
    assert "shortcut" not in scopes.SCOPES                           # left as it was


def test_shortcut_ms_reads_the_adds_time():
    run, expect = cpu_run("small_residual")
    names = shortcut_ms.shortcut_instructions(run.result["hlo"])
    got = shortcut_ms.read(run)
    assert got == pytest.approx(1e3 * sum(run.trace.op_s.get(n, 0.0) for n in names) / 2)
    assert got > 0
    assert got == pytest.approx(expect["metrics"]["shortcut_ms"], rel=1e-9)


def test_a_program_without_shortcuts_reads_as_nothing():
    hlo = gzip.open(TESTDATA / "small_scoped.hlo.txt.gz", "rt").read()
    run = SimpleNamespace(trace=None, result={"hlo": hlo, "window": {"steps": 2}})
    assert shortcut_ms.read(run) is None
    assert shortcut_ms.read(SimpleNamespace(trace=None, result={"hlo": None})) is None


def test_op_names_resolve_as_the_scopes_walk():
    """The reader's own ``op_name`` walk resolves every instruction as
    ``scopes.instruction_scopes`` does: the two classify alike."""
    hlo = gzip.open(TESTDATA / "small_residual.hlo.txt.gz", "rt").read()
    ops = shortcut_ms.op_names(hlo)
    assert {n: scopes.classify(op or "") for n, op in ops.items()} == scopes.instruction_scopes(hlo)
