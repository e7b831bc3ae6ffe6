"""The training job: drive the program's training path on the chip, time it,
and check it against the plain reference.

Set-up builds one object, the jitted step of ``repro.train.trainer.
make_train_step`` over the ``TiledCNNArch`` that ``repro.models.yolo.
make_yolo_tiled_arch`` plans for the cell, with its state, as
``repro.launch.train.run_tiled`` wires them (momentum SGD, replicated state,
donation). The state starts from the benchmark's own weights. Set-up then
drives that step from the seed through the checked steps, each on a different
batch of the pool, through ``repro.runtime.driver.run_training`` and the same
batch source as the window, and hands the same state to the window.

The window is ``run_training`` over N more steps, N set from the last set-up
step's time so that the window lasts about ``--seconds``. Each step takes the
next pooled batch and places it with ``TiledCNNArch.place_batch``: the
host-to-device copy is in the window, as it is for a user whose loader has
decoded images ready. The window runs from the first call of the batch source
to its (N+1)-th call, so it holds N whole steps of the driver loop; one more
step and the driver's closing checkpoint follow outside it.

Once the window has closed and the device's peak memory is read, the state
is freed and the reference follows the checked steps from the same weights
and batches.
"""
from __future__ import annotations

import dataclasses
import gc
import tempfile
import time

import jax
import numpy as np

import checks
import inputs
import reference

STEP_SPAN, SOURCE_SPAN = "bench.step_call", "bench.batch_source"
POOL = 4               # distinct batches the source cycles through
CHECK_STEPS = 3        # steps set-up drives and the reference follows
TARGET_STD = 0.05      # scale of the L2 stand-in's random target


@dataclasses.dataclass
class Program:
    arch: object
    step: object                 # the jitted train step (state donated)
    init: object                 # the jitted init of the state, replicated


def build_program(cfg: dict, cell: dict, layers) -> Program:
    from repro.configs.base import ParallelConfig, TrainConfig
    from repro.models.yolo import make_yolo_tiled_arch
    from repro.train.trainer import make_train_step

    n, m = cell["grid"]
    arch = make_yolo_tiled_arch(
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
    """The program's plan holds the configuration's layers, as stated."""
    if len(program_layers) != len(layers):
        raise ValueError(f"program plans {len(program_layers)} layers, config {len(layers)}")
    for i, (p, l) in enumerate(zip(program_layers, layers)):
        want = (l.size, l.stride, l.cin, l.cout, l.kind == "max")
        got = (p.kernel, p.stride, p.in_channels, p.out_channels, p.pool)
        if got != want or (l.kind == "conv" and (p.act != "leaky" or not p.batch_norm)):
            raise ValueError(f"layer {i}: program {p} against config {l}")


class BatchSource:
    """The driver's ``make_batch``: the next pooled batch, placed on the tile
    mesh. Records the host clock at each call and the time spent inside."""

    def __init__(self, pool, place, offset: int):
        self.pool, self.place, self.offset = pool, place, offset
        self.calls: list[float] = []
        self.spent: list[float] = []

    def __call__(self, step: int) -> dict:
        t0 = time.perf_counter()
        self.calls.append(t0)
        with jax.profiler.TraceAnnotation(SOURCE_SPAN):
            batch = self.place(self.pool[(self.offset + step) % len(self.pool)])
        self.spent.append(time.perf_counter() - t0)
        return batch


class CompileCounter:
    """Host times at which XLA compiled a program, from JAX's monitoring."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.times: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == self.EVENT:
            self.times.append(time.perf_counter())

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self.times)

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._on_event)


class GcPauses:
    """Host times and lengths of Python's garbage collections."""

    def __init__(self):
        self.pauses: list[tuple[float, float]] = []
        self._t0 = 0.0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, _info):
        now = time.perf_counter()
        if phase == "start":
            self._t0 = now
        else:
            self.pauses.append((self._t0, now - self._t0))

    def seconds_between(self, t0: float, t1: float) -> float:
        return sum(d for t, d in self.pauses if t0 <= t <= t1)

    def close(self):
        gc.callbacks.remove(self._on_gc)


def _drive(prog: Program, state, source: BatchSource, steps: int, ckpt_root: str):
    from repro.runtime.driver import DriverConfig, run_training

    def step_call(st, batch):
        with jax.profiler.TraceAnnotation(STEP_SPAN):
            return prog.step(st, batch)

    cfg = DriverConfig(
        ckpt_dir=tempfile.mkdtemp(dir=ckpt_root), ckpt_every=10**9, resume="never",
    )
    return run_training(
        init_state=lambda _key: state, train_step=step_call,
        make_batch=source, steps=steps, cfg=cfg,
    )


def run(ctx, prog: Program | None = None) -> dict:
    """One run of a training cell. ``ctx`` carries the cell, its configuration,
    the seed, the window's seconds, whether to trace, and the devices. A
    caller that runs several seeds in one process passes the built ``prog``."""
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
        # the checked steps: the first alone, so the optimizer's state after
        # it can be read, then the rest; all through the driver and the pool
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
    # which device ops are convolutions is read from the module that ran
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
    failed = restarts + int(hung)
    return {
        "window": window, "memory_peak": peak, "values": values, "sound": sound,
        "attempted": n_steps, "failed": failed, "losses": losses, "ref_losses": ref_traj.losses,
        "hlo": hlo, "phases": phases, "memory_stats": memory_stats,
        # for bench/calibrate.py, which reads controls on the same seeds
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
                     tiles=(1, 1), rows=None) -> checks.Trajectory:
    """The reference's trajectory over the checked steps from ``p0``, on the
    first ``n_steps`` batches of the pool (their first ``rows`` rows, if set)."""
    ref = reference.Reference(cfg, devices, num, tiles)
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
