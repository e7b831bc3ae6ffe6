"""The residual cell's job (``residual_train_job``) and plain reference
(``residual_reference``) at a small size on the CPU: the reference follows
the program's training step through the job's ``build_program`` and
``follow_reference``; a whole run is correct; a run with the timed path
broken underneath, or the control in the program's place, is not."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import checks
import inputs
import residual_reference as reference
import residual_train_job as job
import run_cell
from helpers import chips, small
from test_faults import half_batch, unchanged_state

CELL = "yolov3-62.voc416.train.xla"


@pytest.mark.parametrize("accum", [1, 2])
def test_reference_follows_the_program(accum):
    benchmark, cell, cfg = small(CELL)
    cell.update(grad_accum=accum)
    layers = reference.layers_from_config(cfg)
    prog = job.build_program(cfg, cell, layers)
    devices = jax.devices()[: chips(benchmark, CELL)]
    pool = inputs.make_pool(5, 2, cell["batch"], accum, (cfg["height"], cfg["width"]),
                            reference.out_shape(cfg, cell["batch"])[1:], job.TARGET_STD)
    params = inputs.make_params(5, layers, prog.arch.state_sharding())
    p0 = jax.device_get(params)
    state = prog.init(jax.random.PRNGKey(0))._replace(params=params)
    losses = []
    for k in range(2):
        state, metrics = prog.step(state, prog.arch.place_batch(pool[k]))
        losses.append(float(metrics["loss"]))
        if k == 0:
            grad = jax.tree.map(lambda m, p: m - cfg["decay"] * p, jax.device_get(state.opt["m"]), p0)
    delta = jax.tree.map(lambda a, b: a - b, jax.device_get(state.params), p0)
    want = job.follow_reference(cfg, devices, pool, p0, 2, accum)
    # 43 batch norms in float32, summed in another order: ~1e-5 (see
    # test_reference.py for the gradient's and the change's reasons)
    np.testing.assert_allclose(losses, want.losses, rtol=3e-5)
    got = checks.readings(checks.Trajectory(losses, grad, delta), want)
    assert got["grad_gap"] < 5e-3
    assert got["update_gap"] < 1e-2


def test_a_shortcut_is_the_saved_output_added():
    cfg = dict(small(CELL)[2], depth=5)
    layers = reference.layers_from_config(cfg)
    assert [l.kind for l in layers] == ["conv", "conv", "conv", "conv", "shortcut"]
    assert layers[4].offset == -3 and reference.out_shape(cfg, 2) == (2, 32, 32, 64)


def run_small(hw=64, batch=8):
    benchmark, cell, cfg = small(CELL, hw, batch)
    return run_cell.run(CELL, 2**33 + 9, 0.5, False, benchmark=benchmark,
                        cell=cell, cfg=cfg, require_tpu=False)


def no_shortcut(monkeypatch):
    import repro.core.spatial as spatial

    monkeypatch.setattr(spatial, "add_shortcut", lambda x, skip, skip_halo, out_halo: x)


def test_sound_run_is_correct():
    out = run_small()
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) >= {"train_images_per_s", "setup_s", "peak_hbm_gib"}


@pytest.mark.parametrize("fault", [unchanged_state, half_batch, no_shortcut])
def test_fault_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    out = run_small()
    assert not out["correct"], out["checks"]


def test_control_fails_the_limits():
    benchmark, cell, cfg = small(CELL)
    layers = reference.layers_from_config(cfg)
    devices = jax.devices()[: chips(benchmark, CELL)]
    micro, n = cell["grad_accum"], job.CHECK_STEPS
    pool = inputs.make_pool(11, n, cell["batch"], micro, (cfg["height"], cfg["width"]),
                            reference.out_shape(cfg, cell["batch"])[1:], job.TARGET_STD)
    p0 = jax.device_get(inputs.make_params(11, layers))
    want = job.follow_reference(cfg, devices, pool, p0, n, micro)
    for kind, kw in job.faults(cell).items():
        got = job.follow_reference(cfg, devices, pool, p0, n, micro, **kw)
        values = checks.readings(got, want)
        assert not checks.passed(checks.judge(values, cell["limits"])), (kind, values)
