"""The control comes out not correct against the cell's limits: the
reference with its network computed in the precision below the one the
configuration states (bfloat16 activations, operands and batch norm), its
parameters, momentum and loss sum kept in float32, as a mixed-precision
trainer keeps them. The chip reads it at the cell's own size
(``bench/calibrate.py``); this test keeps it at a size the CPU can hold."""
import jax
import pytest

import checks
import inputs
import reference
import train_job
from helpers import chips, small


@pytest.mark.parametrize("cell_name", ["voc416.train.xla", "coco608.train.2x2"])
def test_control_fails_the_limits(cell_name):
    benchmark, cell, cfg = small(cell_name)
    layers = reference.layers_from_config(cfg)
    devices = jax.devices()[: chips(benchmark, cell_name)]
    micro, n = cell["grad_accum"], train_job.CHECK_STEPS
    pool = inputs.make_pool(11, n, cell["batch"], micro, (cfg["height"], cfg["width"]),
                            reference.out_shape(cfg, cell["batch"])[1:], train_job.TARGET_STD)
    p0 = jax.device_get(inputs.make_params(11, layers))
    want = train_job.follow_reference(cfg, devices, pool, p0, n, micro)
    got = train_job.follow_reference(cfg, devices, pool, p0, n, micro,
                                     num=reference.Numerics(**cell["control"]))
    values = checks.readings(got, want)
    assert not checks.passed(checks.judge(values, cell["limits"])), values
