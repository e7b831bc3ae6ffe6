"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs the fault-tolerant driver (checkpoint/restart, straggler tracking,
watchdog) over the pure ``train_step`` on whatever devices exist locally.
``--reduced`` (default) trains the smoke-scale variant so the launcher is
exercisable on CPU; on a real TPU slice drop ``--full`` in with the
production mesh (same code path the dry-run lowers).

``--arch yolov2-tiled`` launches the paper's distributed tiled-CNN training
through the same unified pipeline: the planner picks the grouping profile
(``--groups auto`` runs the cost-model DP against ``--hw-profile``), the
spatial->data crossover (``--crossover auto|N|none`` - hybrid plans tile
the feature-dominated front and batch-split the weight-dominated tail,
DESIGN.md §7) and the conv backend (``--backend pallas`` uses the MXU
kernel; interpret-mode off TPU), and ``make_train_step`` supplies the
deferred per-batch weight aggregation plus the full trainer tail (clipping,
schedule, optional ``--compress int8`` error-feedback compression of the
weight all-reduce).  ``--wire-codec int8|topk:<k>`` additionally compresses
the per-sample collectives (halo strips, the reshard exchange, pipeline
hand-offs) with error feedback on the recurring backward strips, and the
planner prices its comm terms under the same codec (DESIGN.md §12).

``--arch yolov3-tiled`` runs YOLOv3's Darknet-53 backbone (its first 62
darknet sections: residual units with shortcuts, stride-2 downsampling)
through the same path; ``--depth`` takes up to 62 there, 16 for YOLOv2.
"""
from __future__ import annotations

import argparse
import dataclasses
import tempfile
from typing import Any, Callable

import jax
import numpy as np

from repro.compat import enable_compile_cache
from repro.configs.base import ParallelConfig, ShapeConfig, TrainConfig
from repro.data.synthetic import SyntheticStream, place, synth_batch
from repro.launch.mesh import make_local_mesh, make_production_mesh, make_tile_mesh
from repro.models.registry import ARCH_IDS, get_arch
from repro.parallel.api import sharding_ctx
from repro.runtime.driver import DriverConfig, DriverReport, run_training
from repro.train.trainer import make_train_step

TILED_ARCH = "yolov2-tiled"
TILED_ARCHS = (TILED_ARCH, "yolov3-tiled")


def _tiled_model(arch: str):
    """``(layers(batch_norm=...), make_*_tiled_arch)`` of a tiled-CNN arch id."""
    if arch == TILED_ARCH:
        from repro.models.yolo import make_yolo_tiled_arch, yolov2_16_layers

        return yolov2_16_layers, make_yolo_tiled_arch
    from repro.models.yolov3 import make_yolov3_tiled_arch, yolov3_62_layers

    return yolov3_62_layers, make_yolov3_tiled_arch


def _add_args(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--arch", choices=ARCH_IDS + list(TILED_ARCHS), default="stablelm-1.6b")
    ap.add_argument("--full", action="store_true", help="full config (TPU-scale)")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adamw", choices=["adamw", "adafactor", "sgd"])
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--remat", default="full", choices=["none", "full", "dots"])
    ap.add_argument("--compress", default=None, choices=[None, "int8"],
                    help="gradient compression for the weight all-reduce")
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint directory (default: a fresh temporary "
                         "directory, so a rerun never resumes a stale run)")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--resume", default="auto", choices=["auto", "always", "never"],
                    help="checkpoint resume policy: 'auto' restores the "
                         "newest loadable checkpoint in --ckpt-dir if one "
                         "exists, 'always' requires one, 'never' starts fresh")
    ap.add_argument("--fault-schedule", default=None,
                    help="fault-injection schedule (runtime.faults): "
                         "comma-separated 'kind[:arg]@step' items.  Kinds: "
                         "'drop:<device>@N' (device leaves at step N), "
                         "'add:<device>@N' (device joins), 'slow:<sec>@N' "
                         "(step N stalls <sec> seconds - straggler "
                         "detection), 'fail@N' (step N raises; checkpoint "
                         "restart), 'ckpt-crash[:count]@N' (writer crashes "
                         "count times mid-save), 'corrupt@N' (flip bytes in "
                         "the latest checkpoint).  Example: "
                         "'drop:jetson@5,slow:0.2@8,ckpt-crash@10,corrupt@12'. "
                         "Drops/adds trigger an elastic replan onto the "
                         "surviving devices (tiled arch only; pipeline plans "
                         "re-pack stages onto survivors or degrade to "
                         "spatial/data)")
    ap.add_argument("--mesh", choices=["local", "single", "multi"], default="local")
    ap.add_argument("--seed", type=int, default=0)
    # tiled-CNN (planner) options
    ap.add_argument("--grid", type=int, default=1, help="tiled: n=m tile grid")
    ap.add_argument("--input-hw", type=int, default=64, help="tiled: input H=W")
    ap.add_argument("--depth", type=int, default=8,
                    help="tiled: prefix depth (up to 16 for yolov2-tiled, "
                         "62 for yolov3-tiled)")
    ap.add_argument("--backend", default="xla", choices=["xla", "pallas"],
                    help="tiled: conv compute backend")
    ap.add_argument("--schedule", default="sync", choices=["sync", "overlap", "auto"],
                    help="tiled: executor schedule (overlap = packed halo "
                         "collectives + interior/boundary split; auto = overlap "
                         "only when the backend can hide collectives and the "
                         "modelled hidden term is non-trivial)")
    ap.add_argument("--groups", default="none",
                    help="tiled: grouping profile - 'none', 'auto', or group size int")
    ap.add_argument("--crossover", default="none",
                    help="tiled: spatial->data crossover layer - 'none' (all "
                         "spatial), 'auto' (cost-model choice; joint with the "
                         "grouping DP under --groups auto), or a layer index N")
    ap.add_argument("--pipeline", default="none",
                    help="tiled: pipeline tail over stage device subsets "
                         "(DESIGN.md §11) - 'none', 'auto' (the planner "
                         "weighs bubble + inter-stage transfer against halo "
                         "and reshard traffic), or a stage count S; requires "
                         "--groups auto, and BN layers must stay out of the "
                         "tail (see --no-batch-norm)")
    ap.add_argument("--wire-codec", default="none",
                    help="tiled: per-sample collective codec - 'none', "
                         "'int8' (blockwise absmax, stateless on forward "
                         "halos, error feedback on backward strips and the "
                         "reshard adjoint), or 'topk:<k>' (k a fraction "
                         "0<k<1 or a count); the planner's comm terms are "
                         "priced under the same codec (DESIGN.md §12)")
    ap.add_argument("--no-batch-norm", action="store_true",
                    help="tiled: build the YOLO stack without batch norm "
                         "(required for layers inside pipeline stages: BN's "
                         "cross-device psums cannot run in stage-local "
                         "programs)")
    ap.add_argument("--hw-profile", default="pi3-core",
                    help="tiled: hardware profile for --groups/--crossover auto")
    ap.add_argument("--cluster", default=None,
                    help="tiled: heterogeneous cluster spec, e.g. "
                         "'pi3x3+jetson' - <profile>[x<count>] parts joined "
                         "by '+', filling the tile grid row-major; overrides "
                         "--hw-profile and makespan-balances the tile "
                         "partition to each device's FLOPs (DESIGN.md §8)")


def _resolve_groups(spec: str, n_layers: int):
    if spec in ("none", "0"):        # 0 = per-layer sync, like the example
        return None
    if spec == "auto":
        return "auto"
    from repro.core.tiling import uniform_grouping

    return uniform_grouping(n_layers, int(spec))


def _resolve_crossover(spec: str):
    if spec == "none":
        return None
    if spec == "auto":
        return "auto"
    return int(spec)


def _resolve_pipeline(spec: str):
    if spec == "none":
        return None
    if spec == "auto":
        return "auto"
    try:
        return int(spec)   # check_pipeline_arg validates the count itself
    except ValueError:
        raise SystemExit(
            f"--pipeline must be 'none', 'auto', or a stage count; got {spec!r}"
        ) from None


@dataclasses.dataclass
class TiledRun:
    """What ``run_tiled`` built and did: the planner's arch bundle, the
    train-step pieces the driver ran, and the driver's report."""

    arch: Any
    init_state: Callable
    step_fn: Callable
    make_batch: Callable
    report: DriverReport


def _driver_config(args) -> DriverConfig:
    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="repro_ckpt_")
    print(f"checkpoints: {ckpt_dir} (resume={args.resume})")
    return DriverConfig(
        ckpt_dir=ckpt_dir, ckpt_every=args.ckpt_every,
        log_every=args.log_every, resume=args.resume,
    )


def run_tiled(args) -> TiledRun:
    from repro.core.grouping import parse_cluster_spec

    model_layers, make_arch = _tiled_model(args.arch)
    n_layers = len(model_layers()[: args.depth])
    if not 1 <= args.depth <= len(model_layers()):
        raise SystemExit(
            f"--depth {args.depth}: {args.arch} has {len(model_layers())} layers"
        )
    cluster = (
        parse_cluster_spec(args.cluster, args.grid, args.grid)
        if args.cluster
        else None
    )
    hw = cluster if cluster is not None else args.hw_profile
    pipeline = _resolve_pipeline(args.pipeline)
    arch = make_arch(
        input_hw=(args.input_hw, args.input_hw),
        depth=args.depth,
        n=args.grid,
        m=args.grid,
        groups=_resolve_groups(args.groups, n_layers),
        backend=args.backend,
        schedule=args.schedule,
        hw=hw,
        batch=args.batch,
        crossover=_resolve_crossover(args.crossover),
        pipeline=pipeline,
        microbatches=max(args.grad_accum, 1),
        wire_codec=args.wire_codec,
        batch_norm=not args.no_batch_norm,
    )
    part = arch.plan.partition
    print(
        f"plan: backend={arch.plan.backend} schedule={arch.plan.schedule} "
        f"grid={args.grid}x{args.grid} crossover={arch.plan.crossover} "
        f"groups={[(g.start, g.end, g.mode) for g in arch.plan.groups]}"
        + (f" stages={arch.plan.stages}" if arch.plan.stages else "")
        + (f" wire_codec={arch.plan.wire_codec}"
           if arch.plan.wire_codec != "none" else "")
    )
    print(
        f"partition: rows={part.row_bounds} cols={part.col_bounds} "
        f"uniform={arch.plan.is_uniform}"
        + (f" cluster={args.cluster}" if args.cluster else "")
    )
    pcfg = ParallelConfig(grad_accum=args.grad_accum)
    tcfg = TrainConfig(
        lr=args.lr, optimizer=args.optimizer, steps=args.steps,
        ckpt_every=args.ckpt_every, seed=args.seed,
        grad_compression=args.compress,
    )
    init_state, train_step = make_train_step(arch, pcfg, tcfg)
    # state replicated over the tile mesh from the start; batches placed
    # tile by tile (TiledCNNArch.place_batch) - never gathered on device 0
    init_state = jax.jit(init_state, out_shardings=arch.state_sharding())
    step_fn = jax.jit(train_step, donate_argnums=(0,))
    tgt = arch.target_shape(args.batch)

    def make_batch(step: int) -> dict:
        rng = np.random.default_rng([args.seed, step])
        x = rng.standard_normal((args.batch, args.input_hw, args.input_hw, 3), np.float32)
        t = 0.05 * rng.standard_normal(tgt, np.float32)
        return live["arch"].place_batch({"x": x, "t": t})

    # Elastic replan: a ClusterChange (fault schedule or a real device
    # monitor) rebuilds the plan for the surviving device set and hands the
    # driver a train step jit'd for the new mesh.  The live TrainState
    # carries over (global params; optimizer statistics untouched).
    from repro.core import (
        add_device, drop_device, plan_manifest, replan_stack,
    )
    from repro.models.tiled_cnn import TiledCNNArch
    from repro.models.yolo import l2_loss_local
    from repro.runtime.faults import FaultInjector

    live = {"cluster": cluster, "plan": arch.plan, "arch": arch}

    def replan(ev):
        cl = live["cluster"]
        if cl is None:  # homogeneous grid: materialize a ClusterSpec to edit
            cl = parse_cluster_spec(
                f"{args.hw_profile}x{args.grid * args.grid}", args.grid, args.grid
            )
        cl = drop_device(cl, ev.device) if ev.kind == "drop" else add_device(cl, ev.device)
        new_plan = replan_stack(live["plan"], cl, batch=args.batch)
        new_arch = TiledCNNArch(
            plan=new_plan,
            mesh=make_tile_mesh(new_plan.n, new_plan.m),
            loss_local=l2_loss_local,
        )
        _, new_step = make_train_step(new_arch, pcfg, tcfg)
        live.update(cluster=cl, plan=new_plan, arch=new_arch)
        print(
            f"replan ({ev.kind}:{ev.device}): grid={new_plan.n}x{new_plan.m} "
            f"rows={new_plan.partition.row_bounds} "
            f"cols={new_plan.partition.col_bounds} "
            f"crossover={new_plan.crossover} "
            f"modes={[(g.start, g.end, g.mode) for g in new_plan.groups]}"
            + (f" stages={new_plan.stages}" if new_plan.stages else "")
        )
        return jax.jit(new_step, donate_argnums=(0,)), plan_manifest(new_plan, cl)

    report = run_training(
        init_state=init_state,
        train_step=step_fn,
        make_batch=make_batch,
        steps=args.steps,
        cfg=_driver_config(args),
        seed=args.seed,
        faults=FaultInjector(args.fault_schedule) if args.fault_schedule else None,
        replan=replan,
        plan=plan_manifest(arch.plan, cluster),
    )
    m = report.last_metrics or {}
    print(
        f"done: steps={report.steps_done} restarts={report.restarts} "
        f"replans={report.replans} stragglers={report.straggler_steps} "
        f"loss={m.get('loss', float('nan')):.4f} gnorm={m.get('grad_norm', 0):.3f}"
    )
    return TiledRun(arch, init_state, step_fn, make_batch, report)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__)
    _add_args(ap)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    enable_compile_cache()

    if args.arch in TILED_ARCHS:
        run_tiled(args)
        return 0

    arch = get_arch(args.arch, reduced=not args.full)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    pcfg = ParallelConfig(grad_accum=args.grad_accum, remat=args.remat)
    tcfg = TrainConfig(
        lr=args.lr, optimizer=args.optimizer, steps=args.steps,
        ckpt_every=args.ckpt_every, seed=args.seed,
        grad_compression=args.compress,
    )
    mesh = (
        make_local_mesh()
        if args.mesh == "local"
        else make_production_mesh(multi_pod=(args.mesh == "multi"))
    )

    with sharding_ctx(mesh):
        init_state, train_step = make_train_step(arch, pcfg, tcfg)
        step_fn = jax.jit(train_step, donate_argnums=(0,))
        specs = arch.input_specs(shape)

        def make_batch(step: int) -> dict:
            return place(synth_batch(specs, arch.cfg, args.seed, step))

        from repro.runtime.faults import FaultInjector

        report = run_training(
            init_state=init_state,
            train_step=step_fn,
            make_batch=make_batch,
            steps=args.steps,
            cfg=_driver_config(args),
            seed=args.seed,
            faults=(
                FaultInjector(args.fault_schedule) if args.fault_schedule else None
            ),
        )
    m = report.last_metrics or {}
    print(
        f"done: steps={report.steps_done} restarts={report.restarts} "
        f"stragglers={report.straggler_steps} "
        f"loss={m.get('loss', float('nan')):.4f} gnorm={m.get('grad_norm', 0):.3f}"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
