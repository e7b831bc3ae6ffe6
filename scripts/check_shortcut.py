"""Residual shortcuts on a 2x2 tile grid of 4 fake devices (subprocess
target for ``tests/test_shortcut_exact.py``); prints one JSON object.

- ``plans``: YOLOv3's layers 0-11 at their published widths, 64x64, batch 8,
  through ``make_tiled_loss`` and its gradient, against the plain residual
  reference (``bench/residual_reference.py``) from the same seeded weights:
  the loss's relative gap and each gradient leaf's norm gap, for the
  default plan (a sync at every layer), fused groups that each hold a whole
  residual unit, and a crossover at a unit's edge.
- ``halo``: the asymmetric (1, 0) halo of a 3x3 stride-2 conv through
  ``halo_exchange_1d`` on a 2-shard and a 1-shard axis: the ``ppermute``s
  and the largest gap to each tile's window of the zero-padded map.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, PartitionSpec as P

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "bench"))

import checks  # noqa: E402
import inputs  # noqa: E402
import residual_reference  # noqa: E402
from repro.core.fusion import make_tiled_loss  # noqa: E402
from repro.core.halo import halo_exchange_1d  # noqa: E402
from repro.core.tiling import Group  # noqa: E402
from repro.models.yolo import l2_loss_local  # noqa: E402
from repro.models.yolov3 import make_yolov3_tiled_arch  # noqa: E402

HW, BATCH, DEPTH, SEED = 64, 8, 12, 3
PLANS = {
    "default": dict(),
    "fused_units": dict(groups=[Group(0, 1), Group(2, 4), Group(5, 5), Group(6, 8), Group(9, 11)]),
    "crossover_at_unit_edge": dict(groups=[Group(0, 4), Group(5, 8), Group(9, 11)], crossover=9),
}


def config(hw: int, depth: int) -> dict:
    cfg = json.loads((REPO / "bench" / "configs" / "yolov3-62.voc416.json").read_text())
    cfg.update(height=hw, width=hw, depth=depth)
    return cfg


def compare(arch, cfg, batch: int, seed: int) -> dict:
    """The program's loss and gradient against the plain reference's."""
    layers = residual_reference.layers_from_config(cfg)
    params = jax.device_get(inputs.make_params(seed, layers))
    (pool,) = inputs.make_pool(seed, 1, batch, 1, (cfg["height"], cfg["width"]),
                               residual_reference.out_shape(cfg, batch)[1:], 0.05)
    loss_fn = make_tiled_loss(arch.plan, arch.mesh, l2_loss_local)
    loss, grad = jax.jit(jax.value_and_grad(loss_fn))(params, pool["x"], pool["t"])
    ref = jax.jit(jax.value_and_grad(lambda p: residual_reference._loss(
        p, jnp.asarray(pool["x"]), jnp.asarray(pool["t"]), float(pool["t"].size),
        tuple(layers), residual_reference.REFERENCE, cfg["bn_eps"], True)))
    want_loss, want_grad = ref(params)
    gaps = checks.leaf_gaps(jax.device_get(grad), jax.device_get(want_grad))
    return {"loss_gap": abs(float(loss) - float(want_loss)) / abs(float(want_loss)),
            "grad_gap_median": float(np.median(gaps)), "grad_gap_worst": float(max(gaps))}


def halo_case(shards: int) -> dict:
    mesh = jax.make_mesh((shards,), ("x",), axis_types=(AxisType.Auto,),
                         devices=jax.devices()[:shards])
    f = jax.shard_map(lambda t: halo_exchange_1d(t, 1, 0, "x", dim=1), mesh=mesh,
                      in_specs=P(None, "x"), out_specs=P(None, "x"), check_vma=False)
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (2, 8, 3)))
    y = np.asarray(f(x)).reshape(2, shards, 8 // shards + 1, 3)
    xp = np.pad(x, ((0, 0), (1, 0), (0, 0)))
    t = 8 // shards
    gap = max(float(np.max(np.abs(y[:, i] - xp[:, i * t:(i + 1) * t + 1]))) for i in range(shards))
    eqns = jax.make_jaxpr(f)(x).eqns[0].params["jaxpr"].eqns
    return {"ppermute": sum(e.primitive.name == "ppermute" for e in eqns), "gap": gap}


def main() -> None:
    cfg = config(HW, DEPTH)
    plans = {name: compare(make_yolov3_tiled_arch((HW, HW), DEPTH, 2, 2, batch=BATCH, **kw),
                           cfg, BATCH, SEED)
             for name, kw in PLANS.items()}
    print(json.dumps({"plans": plans, "halo": {str(n): halo_case(n) for n in (1, 2)}}))


if __name__ == "__main__":
    main()
