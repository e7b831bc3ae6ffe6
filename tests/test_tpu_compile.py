"""The Pallas conv kernels compile for a TPU v5e chip at YOLOv2-16's shapes.

Interpret mode (every other kernel test) cannot see what the TPU compiler
refuses: unaligned sublane slices, VMEM overruns, Mosaic layout mismatches.
These cases lower and compile forward, dgrad and wgrad against a described
``v5e:2x2`` topology - no chip attached, nothing runs - at the layer-1,
layer-3, layer-13 and layer-14 shapes of a 416x416 input, on the 1x1 grid
(the whole map) and on one tile of the 2x2 grid. The whole YOLOv2-16 train
step on the 2x2 grid compiles too, with each instruction under the named
scopes of ``repro.obs`` (checked as ``test_tracing.py`` checks the 1x1 grid),
and so does the step on one chip's 1x1 grid, with no collective-permute:
an axis of one shard zero-pads its halo instead of exchanging it. The
YOLOv3 backbone's 62-layer step compiles for one chip at 416x416 and the
microbatch of 32 that ``yolov3-62.voc416.train.xla`` runs, with every
convolution under ``conv`` and every shortcut's add under ``shortcut``.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.kernels.conv2d_tiled.backward import conv2d_dgrad_tile, conv2d_wgrad_tile
from repro.kernels.conv2d_tiled.kernel import conv2d_tile
from repro import obs
from repro.models.yolo import make_yolo_tiled_arch
from repro.models.yolov3 import make_yolov3_tiled_arch
from test_tracing import check_scopes, compile_step, scopes_of

BATCH = 8
# YOLOv2-16 layer -> (input extent at 416x416, K, Cin, Cout)
LAYERS = {1: (416, 3, 3, 32), 3: (208, 3, 32, 64), 13: (26, 3, 256, 512), 14: (26, 1, 512, 256)}
CASES = [(kind, layer, grid) for kind in ("fwd", "dgrad", "wgrad") for layer in LAYERS for grid in (1, 2)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no description here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


@pytest.mark.parametrize("kind,layer,grid", CASES, ids=[f"{k}-L{l}-{g}x{g}" for k, l, g in CASES])
def test_conv_kernel_compiles_for_v5e(one_chip, kind, layer, grid):
    extent, k, cin, cout = LAYERS[layer]
    o = extent // grid                       # output rows/cols of this tile
    i = o + k - 1                            # halo-extended input extent
    x = _sds((BATCH, i, i, cin), one_chip)
    w = _sds((k, k, cin, cout), one_chip)
    g = _sds((BATCH, o, o, cout), one_chip)
    if kind == "fwd":
        fn = lambda x_, w_: conv2d_tile(x_, w_, None, act="leaky")
        args = (x, w)
    elif kind == "dgrad":
        fn = lambda g_, w_: conv2d_dgrad_tile(g_, w_, (i, i))
        args = (g, w)
    else:
        fn = lambda x_, g_: conv2d_wgrad_tile(x_, g_, k)
        args = (x, g)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("schedule", ["sync", "overlap"])
def test_train_step_scopes_for_v5e_2x2(topo, schedule):
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("th", "tw"))
    arch = make_yolo_tiled_arch((64, 64), 16, 2, 2, schedule=schedule, batch=4, mesh=mesh)
    ops = compile_step(arch, 4)
    seen = check_scopes(ops, arch.plan)
    assert seen >= {"convolution", "select-and-scatter", "reduce-window", "all-reduce",
                    "collective-permute", obs.OPTIMIZER}
    # the halo exchanges of group inputs, forward and reversed in the backward
    halo_layers = {scopes_of(n)[0] for op, n in ops if op.startswith("collective-permute")}
    assert halo_layers <= {g.start for g in arch.plan.groups} and 0 in halo_layers


def test_train_step_1x1_for_v5e_has_no_collective_permute(topo):
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("th", "tw"))
    arch = make_yolo_tiled_arch((64, 64), 16, 1, 1, batch=4, mesh=mesh)
    ops = compile_step(arch, 4)
    seen = check_scopes(ops, arch.plan)
    assert seen >= {"convolution", "select-and-scatter", "reduce-window", obs.OPTIMIZER}
    assert not [op for op, _ in ops if op.startswith("collective-permute")]


def test_yolov3_step_for_v5e_at_416(topo):
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("th", "tw"))
    arch = make_yolov3_tiled_arch((416, 416), 62, 1, 1, batch=32, mesh=mesh)
    ops = compile_step(arch, 32)
    seen = check_scopes(ops, arch.plan)
    assert seen >= {"convolution", obs.SHORTCUT, obs.OPTIMIZER}
    conv_layers = {scopes_of(n)[0] for op, n in ops if op == "convolution"}
    assert conv_layers == {i for i, l in enumerate(arch.plan.layers) if l.is_conv}
    added = {scopes_of(n)[0] for op, n in ops if obs.SHORTCUT in scopes_of(n)[1]}
    assert added == {i for i, l in enumerate(arch.plan.layers) if l.shortcut}
