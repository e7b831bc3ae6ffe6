"""Residual shortcuts against the plain residual reference of the benchmark
(``bench/residual_reference.py``: untiled, float32, whole-batch BN), from
the same seeded weights and batches, on the CPU.

YOLOv3's layers 0-11 at their published widths (64x64, batch 8) on the 1x1
grid in-process, for the default plan, fused groups that each hold a whole
residual unit, and a crossover at a unit's edge; the whole 62-section stack
at 32x32; the forward-only serving twin. The 2x2 grid is checked in
``test_shortcut_grid.py``.

Tolerances, as ``bench/tests/test_reference.py`` sets them for YOLOv2:
the loss to a relative 3e-5, since twelve or more batch norms in float32,
each summed in another order (tiles, XLA's fusions), move it by ~1e-5; the
median gradient leaf's norm gap under 5e-3 and the worst leaf's under 1e-2,
since batch-norm scale gradients are sums that cancel to a few digits.
"""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "bench"))

import checks  # noqa: E402
import inputs  # noqa: E402
import residual_reference  # noqa: E402
from repro.core.fusion import make_tiled_infer, make_tiled_loss  # noqa: E402
from repro.core.spatial import stack_reference  # noqa: E402
from repro.core.tiling import Group  # noqa: E402
from repro.models.yolo import l2_loss_local  # noqa: E402
from repro.models.yolov3 import make_yolov3_tiled_arch  # noqa: E402

LOSS_RTOL, GRAD_MEDIAN, GRAD_WORST = 3e-5, 5e-3, 1e-2
PLANS = {
    "default": dict(),
    "fused_units": dict(groups=[Group(0, 1), Group(2, 4), Group(5, 5), Group(6, 8), Group(9, 11)]),
    "crossover_at_unit_edge": dict(groups=[Group(0, 4), Group(5, 8), Group(9, 11)], crossover=9),
}


def config(hw, depth):
    cfg = json.loads((REPO / "bench" / "configs" / "yolov3-62.voc416.json").read_text())
    cfg.update(height=hw, width=hw, depth=depth)
    return cfg


def seeded(cfg, batch, seed):
    layers = residual_reference.layers_from_config(cfg)
    params = jax.device_get(inputs.make_params(seed, layers))
    (pool,) = inputs.make_pool(seed, 1, batch, 1, (cfg["height"], cfg["width"]),
                               residual_reference.out_shape(cfg, batch)[1:], 0.05)
    return layers, params, pool


def assert_follows(arch, cfg, batch, seed):
    layers, params, pool = seeded(cfg, batch, seed)
    loss, grad = jax.jit(jax.value_and_grad(make_tiled_loss(arch.plan, arch.mesh, l2_loss_local)))(
        params, pool["x"], pool["t"])
    want_loss, want_grad = jax.jit(jax.value_and_grad(lambda p: residual_reference._loss(
        p, jnp.asarray(pool["x"]), jnp.asarray(pool["t"]), float(pool["t"].size),
        tuple(layers), residual_reference.REFERENCE, cfg["bn_eps"], True)))(params)
    assert float(loss) == pytest.approx(float(want_loss), rel=LOSS_RTOL)
    gaps = checks.leaf_gaps(jax.device_get(grad), jax.device_get(want_grad))
    assert np.median(gaps) < GRAD_MEDIAN and max(gaps) < GRAD_WORST, gaps


@pytest.mark.parametrize("plan", list(PLANS))
def test_1x1_follows_the_reference(plan):
    arch = make_yolov3_tiled_arch((64, 64), 12, 1, 1, batch=8, **PLANS[plan])
    assert_follows(arch, config(64, 12), 8, seed=3)


def test_whole_stack_follows_the_reference():
    arch = make_yolov3_tiled_arch((32, 32), 62, 1, 1, batch=2)
    assert_follows(arch, config(32, 62), 2, seed=4)


def test_serving_twin_follows_the_forward():
    """The forward-only plan, with BN frozen at the calibration batch's
    statistics, reproduces the training forward on that batch."""
    arch = make_yolov3_tiled_arch((16, 16), 9, 1, 1, batch=2)
    _, params, pool = seeded(config(16, 9), 2, seed=5)
    x = jnp.asarray(pool["x"])
    frozen = jax.jit(arch.serve_params)(params, x)
    y = jax.jit(make_tiled_infer(arch.serve_plan(), arch.mesh))(frozen, x)
    want = jax.jit(lambda p: stack_reference(x, p, arch.plan.layers))(params)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-4, atol=1e-4)
