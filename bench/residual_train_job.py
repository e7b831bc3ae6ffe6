"""The training job of a cell whose network has residual shortcuts
(``yolov3-62.voc416``): ``train_job``'s run, with the program planned by
``repro.models.yolov3.make_yolov3_tiled_arch`` and checked against the plain
residual reference (``residual_reference.py``).

Set-up, the checked steps, the window and the comparison are
``train_job``'s: the same batch source, driver loop, compile counter and
collector log, and the same weights, batches and readings
(``inputs``, ``checks``). Run as a script, it takes the cell's readings on
the chip, as ``calibrate.py`` does for ``train_job``:

    python3 bench/residual_train_job.py --workload <name> --seeds 1,2,3 [--control 3]

On the first ``--control`` seeds the control (the reference's network in
the cell's ``control`` numerics) and the faults planted in the reference
(half of the batch left out; each shortcut left out) are put in the
program's place. Every reading goes to ``bench_out/calibrate.<name>.jsonl``.
"""
from __future__ import annotations

import dataclasses
import tempfile
import time

import jax
import numpy as np

import checks
import inputs
import residual_reference as reference
from train_job import POOL, CHECK_STEPS, TARGET_STD, BatchSource, CompileCounter, GcPauses, _drive


@dataclasses.dataclass
class Program:
    arch: object
    step: object                 # the jitted train step (state donated)
    init: object                 # the jitted init of the state, replicated


def build_program(cfg: dict, cell: dict, layers) -> Program:
    from repro.configs.base import ParallelConfig, TrainConfig
    from repro.models.yolov3 import make_yolov3_tiled_arch
    from repro.train.trainer import make_train_step

    n, m = cell["grid"]
    arch = make_yolov3_tiled_arch(
        (cfg["height"], cfg["width"]), cfg["depth"], n, m,
        backend=cell["backend"], schedule=cell["schedule"], batch=cell["batch"],
    )
    _check_layers(arch.plan.layers, layers)
    if cfg["momentum"] != 0.9 or cfg["lr_floor"] != 0.1:
        raise ValueError("the trainer's sgd has momentum 0.9 and its cosine floor 0.1 built in")
    tcfg = TrainConfig(
        lr=cfg["learning_rate"], weight_decay=cfg["decay"], warmup=cfg["burn_in"],
        optimizer="sgd", grad_clip=cfg["grad_clip"], steps=cfg["max_batches"],
    )
    init_state, train_step = make_train_step(arch, ParallelConfig(grad_accum=cell["grad_accum"]), tcfg)
    step = jax.jit(train_step, donate_argnums=(0,))
    init = jax.jit(init_state, out_shardings=arch.state_sharding())
    return Program(arch, step, init)


def _check_layers(program_layers, layers) -> None:
    """The program's plan holds the configuration's sections, as stated."""
    if len(program_layers) != len(layers):
        raise ValueError(f"program plans {len(program_layers)} layers, config {len(layers)}")
    for i, (p, l) in enumerate(zip(program_layers, layers)):
        want = (l.size, l.stride, l.cin, l.cout, l.offset)
        got = (p.kernel, p.stride, p.in_channels, p.out_channels, p.shortcut)
        conv_ok = l.kind != "conv" or (p.act == "leaky" and p.batch_norm and p.is_conv)
        if got != want or not conv_ok or p.pool:
            raise ValueError(f"layer {i}: program {p} against config {l}")


def run(ctx, prog: Program | None = None) -> dict:
    """One run of the cell; ``train_job.run``'s steps and result."""
    cfg, cell = ctx.cfg, ctx.cell
    layers = reference.layers_from_config(cfg)
    counter, pauses = CompileCounter(), GcPauses()
    prog = prog or build_program(cfg, cell, layers)
    batch, micro, n_check = cell["batch"], cell["grad_accum"], CHECK_STEPS
    pool = inputs.make_pool(
        ctx.seed, POOL, batch, micro, (cfg["height"], cfg["width"]),
        reference.out_shape(cfg, batch)[1:], TARGET_STD, device=ctx.devices[0],
    )
    place = prog.arch.place_batch

    params0 = inputs.make_params(ctx.seed, layers, prog.arch.state_sharding())
    p0 = jax.device_get(params0)
    state = prog.init(jax.random.PRNGKey(0))
    _check_params(state.params, params0)
    state = state._replace(params=params0)
    del params0
    t_built = time.perf_counter()

    with tempfile.TemporaryDirectory(prefix="bench_ckpt_") as ckpt_root:
        rep = _drive(prog, state, BatchSource(pool, place, 0), 1, ckpt_root)
        m1 = jax.device_get(rep.final_state.opt["m"])
        losses, restarts = list(rep.losses), rep.restarts
        rep = _drive(prog, rep.final_state, BatchSource(pool, place, 1), n_check - 1, ckpt_root)
        losses += rep.losses
        restarts += rep.restarts
        p_end = jax.device_get(rep.final_state.params)
        step_s = rep.step_times[-1]
        n_steps = max(2, round(ctx.seconds / step_s))

        bs = prog.arch.batch_shardings()
        abstract = (_abstract(rep.final_state),
                    {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=bs[k]) for k, v in pool[0].items()})
        t_checked = time.perf_counter()
        source = BatchSource(pool, place, n_check)
        with ctx.tracer():
            rep = _drive(prog, rep.final_state, source, n_steps + 1, ckpt_root)
        restarts += rep.restarts
        hung = rep.hung
    t_first, t_end = source.calls[0], source.calls[n_steps]
    window = {
        "steps": n_steps, "images": n_steps * batch, "seconds": t_end - t_first,
        "source_s": source.spent[:n_steps], "t_first": t_first,
        "compiles": counter.between(t_first, t_end),
        "gc_s": pauses.seconds_between(t_first, t_end),
    }
    counter.close()
    pauses.close()
    peak = ctx.memory_peak()
    memory_stats = ctx.devices[0].memory_stats()
    hlo = prog.step.lower(*abstract).compile().as_text() if ctx.trace else None
    t_window = time.perf_counter()
    del rep, state
    prog_traj = checks.Trajectory(
        losses=losses[:n_check],
        grad=jax.tree.map(lambda m, p: m - cfg["decay"] * p, m1, p0),
        delta=jax.tree.map(lambda a, b: a - b, p_end, p0),
    )
    ref_traj = follow_reference(cfg, ctx.devices, pool, p0, n_check, micro)
    values = checks.readings(prog_traj, ref_traj)
    phases = {"checked_steps_s": t_checked - t_built, "window_and_close_s": t_window - t_checked,
              "reference_s": time.perf_counter() - t_window}
    sound = restarts == 0 and not hung and len(losses) == n_check
    return {
        "window": window, "memory_peak": peak, "values": values, "sound": sound,
        "attempted": n_steps, "failed": restarts + int(hung), "losses": losses,
        "ref_losses": ref_traj.losses, "hlo": hlo, "phases": phases, "memory_stats": memory_stats,
        "pool": pool, "p0": p0, "ref_traj": ref_traj, "prog_traj": prog_traj,
    }


def _abstract(tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding), tree)


def _check_params(program_params, params) -> None:
    got = jax.tree.map(lambda a: (a.shape, a.dtype), program_params)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if got != want:
        raise ValueError("the benchmark's weights do not fit the program's parameter tree")


def follow_reference(cfg, devices, pool, p0, n_steps, micro, num=reference.REFERENCE,
                     tiles=(1, 1), rows=None, shortcuts=True) -> checks.Trajectory:
    """The reference's trajectory over the checked steps from ``p0``, on the
    first ``n_steps`` batches of the pool (their first ``rows`` rows, if set).
    ``shortcuts=False`` leaves each shortcut out. The residual reference
    convolves whole maps: ``tiles`` is (1, 1)."""
    if tuple(tiles) != (1, 1):
        raise ValueError("the residual reference has no tile-by-tile fault")
    ref = reference.Reference(cfg, devices, num, shortcuts)
    params, mom = ref.place_params(p0), ref.place_params(jax.tree.map(np.zeros_like, p0))
    losses, grad = [], None
    for k in range(n_steps):
        b = pool[k]
        x, t = ref.place_batch(b["x"][:rows], b["t"][:rows])
        loss, g, params, mom = ref.step(params, mom, x, t, k, micro)
        losses.append(loss)
        if grad is None:
            grad = jax.device_get(g)
        del g, x, t
    p_end = jax.device_get(params)
    f32 = lambda tree: jax.tree.map(lambda a: np.asarray(a, np.float32), tree)
    delta = jax.tree.map(lambda a, b: a - b, f32(p_end), p0)
    return checks.Trajectory(losses=losses, grad=f32(grad), delta=delta)


def summary(result: dict) -> dict:
    """The end-to-end numbers of a run."""
    w = result["window"]
    return {
        "train_images_per_s": w["images"] / w["seconds"],
        "peak_hbm_gib": result["memory_peak"] / 2**30,
    }


def faults(cell: dict) -> dict:
    """The control and the faults planted in the reference, as keywords of
    ``follow_reference``."""
    return {"control": dict(num=reference.Numerics(**cell["control"])),
            "half_batch": dict(rows=cell["batch"] // 2),
            "no_shortcut": dict(shortcuts=False)}


def main(argv=None) -> int:
    import argparse
    import json
    import sys

    import calibrate
    import run_cell

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control", type=int, default=0,
                    help="read the control and the faults on the first N seeds")
    args = ap.parse_args(argv)
    entry, cell, cfg = run_cell.load_cell(args.workload)
    devices = run_cell.init_jax(entry["chips"], require_tpu=True)
    sys.path.insert(0, str(run_cell.ROOT / "src"))
    out_dir = run_cell.ROOT / "bench_out"
    out_dir.mkdir(exist_ok=True)
    log = open(out_dir / f"calibrate.{args.workload}.jsonl", "a")

    def emit(rec: dict) -> None:
        line = json.dumps(rec)
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()

    prog = build_program(cfg, cell, reference.layers_from_config(cfg))
    for i, seed in enumerate(calibrate._seeds(args.seeds)):
        t0 = time.perf_counter()
        ctx = run_cell.Context(args.workload, cell, cfg, seed, 0.0, False, devices)
        res = run(ctx, prog)
        emit({"kind": "program", "seed": seed, "sound": res["sound"], **res["values"],
              "losses": res["losses"], "ref_losses": res["ref_losses"],
              **calibrate.norms(res["prog_traj"], "got"), **calibrate.norms(res["ref_traj"], "ref"),
              "peak": res["memory_peak"], "phases": res["phases"], "s": time.perf_counter() - t0})
        if i >= args.control:
            continue
        pool, p0, want = res["pool"], res["p0"], res["ref_traj"]
        del res
        for kind, kw in faults(cell).items():
            t0 = time.perf_counter()
            got = follow_reference(cfg, devices, pool, p0, CHECK_STEPS, cell["grad_accum"], **kw)
            emit({"kind": kind, "seed": seed, **checks.readings(got, want),
                  "losses": got.losses, "ref_losses": want.losses,
                  **calibrate.norms(got, "got"), "s": time.perf_counter() - t0})
    log.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
