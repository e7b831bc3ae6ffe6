"""End-to-end driver: distributed tiled training of the paper's network.

Trains a YOLOv2-16 prefix with the paper's full pipeline through the
unified planner -> executor -> trainer stack:

  planner  build_stack_plan picks the grouping profile (--groups auto runs
           the cost-model DP against --profile) and the conv backend
           (--backend pallas uses the MXU kernel, interpret-mode off TPU);
  executor shard_map'd fused grouped stacks with ppermute halo exchange;
  trainer  make_train_step supplies TrainState, deferred per-batch weight
           aggregation (one psum per batch, paper §4.1), global-norm
           clipping, cosine/warmup LR, and optional int8 error-feedback
           compression of the weight all-reduce (--compress int8);

all under the fault-tolerant runtime driver (checkpoint/restart +
straggler tracking).

On a 4-device grid this runs 2x2 tiles (set XLA_FLAGS before launch or run
on real hardware); on one device it runs the identical 1x1-tiled code.

Run:  PYTHONPATH=src python examples/train_yolo_tiled.py --steps 200
"""
import argparse
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ParallelConfig, TrainConfig
from repro.core.tiling import uniform_grouping
from repro.models.yolo import make_yolo_tiled_arch, yolov2_16_layers
from repro.runtime.driver import DriverConfig, run_training
from repro.train.trainer import make_train_step


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--hw", type=int, default=64, help="input H=W")
    ap.add_argument("--batch", type=int, default=4, help="global batch (all microbatches)")
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--layers", type=int, default=8, help="YOLO prefix depth")
    ap.add_argument("--grid", type=int, default=1, help="tile grid (n=m)")
    ap.add_argument("--group", default="0",
                    help="'auto' = cost-model DP; 0 = per-layer sync; K = uniform size K")
    ap.add_argument("--profile", default="pi3-core",
                    help="hardware profile for --group auto")
    ap.add_argument("--backend", default="xla", choices=["xla", "pallas"])
    ap.add_argument("--compress", default=None, choices=[None, "int8"])
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint directory (default: a fresh temporary one)")
    args = ap.parse_args()
    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="yolo_tiled_ckpt_")

    depth = len(yolov2_16_layers()[: args.layers])
    if args.group == "auto":
        groups = "auto"
    elif int(args.group) == 0:
        groups = None
    else:
        groups = uniform_grouping(depth, int(args.group))

    arch = make_yolo_tiled_arch(
        input_hw=(args.hw, args.hw),
        depth=depth,
        n=args.grid,
        m=args.grid,
        groups=groups,
        backend=args.backend,
        hw=args.profile,
        batch=args.batch,
    )
    print(
        f"plan: backend={arch.plan.backend} "
        f"groups={[(g.start, g.end) for g in arch.plan.groups]}"
    )

    pcfg = ParallelConfig(grad_accum=args.microbatches)
    tcfg = TrainConfig(
        lr=args.lr, optimizer="sgd",          # darknet's optimizer
        warmup=min(20, args.steps // 10), steps=args.steps,
        grad_compression=args.compress,
    )
    init_state, train_step = make_train_step(arch, pcfg, tcfg)
    step_fn = jax.jit(train_step, donate_argnums=(0,))
    tgt = arch.target_shape(args.batch)

    def make_batch(step):
        rng = np.random.default_rng([7, step])
        x = rng.standard_normal((args.batch, args.hw, args.hw, 3), np.float32)
        # regression target: a fixed random linear map of the input stats
        t = 0.05 * rng.standard_normal(tgt, np.float32)
        return {"x": jnp.asarray(x), "t": jnp.asarray(t)}

    report = run_training(
        init_state=init_state,
        train_step=step_fn,
        make_batch=make_batch,
        steps=args.steps,
        cfg=DriverConfig(ckpt_dir=ckpt_dir, ckpt_every=50, log_every=25),
    )
    warm = report.step_times[5:] or report.step_times
    print(
        f"done: steps={report.steps_done} restarts={report.restarts} "
        f"final loss={report.last_metrics['loss']:.6f} "
        f"mean step {np.mean(warm) * 1e3:.1f}ms"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
