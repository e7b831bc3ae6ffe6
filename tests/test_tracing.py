"""The program's profiler spans and named scopes (``repro.obs``).

Host spans are read back from a ``jax.profiler`` trace on the CPU; scopes
from the ``op_name`` metadata of the compiled YOLOv2-16 tiled train step on
the CPU's 1x1 grid. The 2x2 grid's step, compiled against a described
``v5e:2x2`` topology, is checked by ``test_tpu_compile.py`` with these
helpers (all tests that describe the topology live in that one file).
"""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.base import ParallelConfig, TrainConfig
from repro.models.yolo import make_yolo_tiled_arch
from repro.runtime.driver import DriverConfig, run_training
from repro.train.trainer import make_train_step

HOST = {obs.STEP, obs.MAKE_BATCH, obs.DISPATCH, obs.WAIT, obs.METRICS,
        obs.CHECKPOINT, obs.RESTORE, obs.REPLAN, obs.PLACE_BATCH}


def traced(tmp_path, fn):
    """Run ``fn`` under the profiler; the program's host spans it recorded,
    as ``(start_ns, end_ns, name, stats)`` sorted by start."""
    from jax.profiler import ProfileData

    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST:
                        out.append((e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats)))
    return sorted(out, key=lambda e: (e[0], -e[1]))


def tiny_driver(tmp_path, steps, fault_at=None):
    step_fn = jax.jit(lambda s, b: (s + b["x"].sum(), {"loss": s * 2.0}))
    failed = []

    def hook(step):
        if step == fault_at and not failed:
            failed.append(step)
            raise RuntimeError("injected")

    cfg = DriverConfig(ckpt_dir=str(tmp_path / "ckpt"), ckpt_every=2, async_ckpt=False,
                       resume="never")
    return lambda: run_training(
        init_state=lambda key: jnp.zeros((), jnp.float32), train_step=step_fn,
        make_batch=lambda step: {"x": jnp.full((3,), float(step))}, steps=steps,
        cfg=cfg, fault_hook=hook,
    )


def inside(events, outer):
    return [e for e in events if e[2] != obs.STEP and outer[0] <= e[0] and e[1] <= outer[1]]


def test_driver_spans_each_step(tmp_path):
    events = traced(tmp_path, tiny_driver(tmp_path, steps=3))
    steps = [e for e in events if e[2] == obs.STEP]
    assert [e[3]["step_num"] for e in steps] == [0, 1, 2]
    body = [obs.MAKE_BATCH, obs.DISPATCH, obs.WAIT, obs.METRICS]
    for s in steps:
        names = [e[2] for e in inside(events, s)]
        # steps 1 and 2 checkpoint: every second step, and the last
        assert names == body + ([obs.CHECKPOINT] if s[3]["step_num"] in (1, 2) else [])
    saves = [e[3]["step"] for e in events if e[2] == obs.CHECKPOINT]
    assert saves == [1, 2]
    assert not [e for e in events if e[2] in (obs.RESTORE, obs.REPLAN)]


def test_driver_restore_span_after_a_failed_step(tmp_path):
    events = traced(tmp_path, tiny_driver(tmp_path, steps=4, fault_at=3))
    names = [e[2] for e in events]
    (restore,) = [e for e in events if e[2] == obs.RESTORE]
    assert restore[3]["step"] == 3
    # the failed attempt of step 3 ends before its batch; the restore follows
    # it, and step 1's checkpoint brings the loop back to replay steps 2 and 3
    failed = [e for e in events if e[2] == obs.STEP and e[3]["step_num"] == 3]
    assert len(failed) == 2
    assert [e[2] for e in inside(events, failed[0])] == []
    assert failed[0][1] <= restore[0] <= failed[1][0]
    assert [e[3]["step_num"] for e in events if e[2] == obs.STEP] == [0, 1, 2, 3, 2, 3]
    assert names.count(obs.DISPATCH) == 5


def test_place_batch_span_carries_the_bytes(tmp_path):
    arch = make_yolo_tiled_arch((32, 32), 4, 1, 1, batch=2)
    batch = {"x": np.ones((2, 32, 32, 3), np.float32),
             "t": np.ones(arch.target_shape(2), np.float32)}
    events = traced(tmp_path, lambda: jax.block_until_ready(arch.place_batch(batch)))
    (span,) = [e for e in events if e[2] == obs.PLACE_BATCH]
    assert span[3]["bytes"] == batch["x"].nbytes + batch["t"].nbytes


# -- named scopes in the compiled step ---------------------------------------

INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = \S+ ([\w\-]+)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')
LAYER = re.compile(r"^layer(\d\d)$")


def scopes_of(op_name):
    """``(layer index, scopes)`` of an op_name: the plan's layer (0-based)
    and every ``repro.obs`` scope among its components."""
    layer, found = None, []
    for part in op_name.split("/")[:-1]:
        while re.match(r"^[\w.\-]+\(.*\)$", part):
            part = part[part.index("(") + 1:-1]
        m = LAYER.match(part)
        if m:
            layer = int(m.group(1)) - 1
        elif part in obs.SCOPES:
            found.append(part)
    return layer, found


def compiled_ops(text):
    """``(opcode, op_name)`` of every instruction with metadata, fused ones too."""
    out = []
    for line in text.splitlines():
        m, n = INSTR.match(line), OP_NAME.search(line)
        if m and n:
            out.append((m.group(2), n.group(1)))
    return out


def compile_step(arch, batch):
    init, step = make_train_step(arch, ParallelConfig(grad_accum=1), TrainConfig(optimizer="sgd", steps=10))
    ss = arch.state_sharding()
    state = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=ss),
                         jax.eval_shape(init, jax.random.PRNGKey(0)))
    bs = arch.batch_shardings()
    xt = {"x": (batch, *arch.plan.map_hw[0], 3), "t": arch.target_shape(batch)}
    batch_sds = {k: jax.ShapeDtypeStruct(v, jnp.float32, sharding=bs[k]) for k, v in xt.items()}
    return compiled_ops(jax.jit(step).lower(state, batch_sds).compile().as_text())


def check_scopes(ops, plan):
    pools = {i for i, l in enumerate(plan.layers) if l.pool}
    seen = set()
    for opcode, name in ops:
        layer, found = scopes_of(name)
        kind = opcode.replace("-start", "").replace("-done", "")
        # the CPU expands the pool backward's select-and-scatter to a scatter
        want = {"convolution": obs.CONV, "select-and-scatter": obs.POOL, "scatter": obs.POOL,
                "reduce-window": obs.POOL, "collective-permute": obs.HALO}.get(kind)
        if want:
            seen.add(kind)
            assert found[-1:] == [want], (opcode, name)
            assert layer is not None, (opcode, name)
            if want != obs.HALO:
                assert (layer in pools) == (want == obs.POOL), (opcode, name)
        if kind == "all-reduce" and layer is not None:
            seen.add(kind)
            assert found[-1:] == [obs.BN] and layer not in pools, (opcode, name)
        if obs.OPTIMIZER in found:
            seen.add(obs.OPTIMIZER)
            assert layer is None, (opcode, name)
        if set(found) & {obs.CONV, obs.BN, obs.POOL, obs.HALO, obs.SHORTCUT}:
            assert layer is not None, (opcode, name)
        # a shortcut's add and crop sit under its own scope, in its own layer
        if obs.SHORTCUT in found:
            seen.add(obs.SHORTCUT)
            assert found[-1:] == [obs.SHORTCUT] and plan.layers[layer].shortcut, (opcode, name)
        if want == obs.CONV:
            assert not plan.layers[layer].shortcut, (opcode, name)
    return seen


def test_scopes_in_the_compiled_step_1x1():
    arch = make_yolo_tiled_arch((64, 64), 16, 1, 1, batch=2)
    seen = check_scopes(compile_step(arch, 2), arch.plan)
    assert seen >= {"convolution", "scatter", "reduce-window", "all-reduce", obs.OPTIMIZER}
