"""The benchmark's plain reference against the program's tiled training step,
at a small size on the CPU, where both compute in float32."""
import jax
import numpy as np
import pytest

import checks
import inputs
import reference
import train_job
from helpers import chips, small


@pytest.mark.parametrize("cell_name,accum", [
    ("voc416.train.xla", 1), ("voc416.train.xla", 2), ("coco608.train.2x2", 1),
])
def test_reference_follows_the_program(cell_name, accum):
    benchmark, cell, cfg = small(cell_name)
    cell.update(grad_accum=accum)
    layers = reference.layers_from_config(cfg)
    prog = train_job.build_program(cfg, cell, layers)
    devices = jax.devices()[: chips(benchmark, cell_name)]
    pool = inputs.make_pool(5, 2, cell["batch"], accum, (cfg["height"], cfg["width"]),
                            reference.out_shape(cfg, cell["batch"])[1:], train_job.TARGET_STD)
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
    want = train_job.follow_reference(cfg, devices, pool, p0, 2, accum)
    np.testing.assert_allclose(losses, want.losses, rtol=1e-5)
    got = checks.readings(checks.Trajectory(losses, grad, delta), want)
    # Batch-norm scale gradients are sums that cancel to a few digits; in
    # float32, summed in another order (microbatches, tiles) they move by
    # ~5e-4 of their norm. The change over two steps cancels most digits of p.
    assert got["grad_gap"] < 5e-3
    assert got["update_gap"] < 1e-2


def test_learning_rate_is_the_trainers_cosine():
    from repro.optim.schedules import cosine_schedule

    hp = reference.Hyper(1e-3, 0.9, 5e-4, burn_in=10, max_batches=100, grad_clip=1.0,
                         lr_floor=0.1, bn_eps=1e-5)
    for step in (0, 5, 9, 10, 50, 100, 150):
        want = float(cosine_schedule(np.int32(step), 10, 100, 1e-3))
        assert reference.learning_rate(step, hp) == pytest.approx(want, rel=1e-6)


def test_tiles_leave_the_exchange_out():
    """Convolved tile by tile, only the tile borders differ from the whole map."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 8, 2))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 2, 3))
    whole = reference._conv(x, w, 1, reference.REFERENCE, (1, 1))
    tiled = reference._conv(x, w, 1, reference.REFERENCE, (2, 2))
    diff = np.abs(np.asarray(whole - tiled)).max(axis=(0, 3))
    inner = np.ones((8, 8), bool)
    inner[[3, 4], :] = inner[:, [3, 4]] = False
    assert diff[inner].max() == 0 and diff[~inner].max() > 0
