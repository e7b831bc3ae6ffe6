"""Smoke gate on the TPU: the paper's YOLOv2-16 trains at 416x416 through the
normal launcher and agrees with the untiled reference.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # 2x2 tile grid over four chips

One chip: ``repro.launch.train --arch yolov2-tiled`` (1x1 grid, all 16
layers at their published widths, batch 8, 3 SGD steps, BN on) on the xla
and the pallas conv backends; the first step's loss and grads of both
against ``reference_loss``; then BN statistics are frozen and 8 requests
are served through ``make_serve_engine`` and checked against
``reference_forward``.  ``--four-chips`` runs only the 2x2-grid training
(sync schedule, xla) and its comparison with the 1x1 untiled reference on
one of the chips.  Comparisons run both sides under
``jax.default_matmul_precision("float32")`` at the tolerances below
(DESIGN.md, "Precision on the chip"); default-precision differences are
printed for information.

Lines before the last are information.  The last line is one JSON object,
``{"ok": true, "device": {...}}``.  Exits nonzero, with no such line, when
JAX finds no TPU or any check fails.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import tempfile
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

HW, DEPTH, BATCH, STEPS, SEED = 416, 16, 8, 3, 0
LOSS_RTOL = 1e-4      # f32: |loss - ref| / |ref|
GRAD_RTOL = 3e-2      # f32: per leaf, |g - ref|_2 / |ref|_2
SERVE_RTOL = 1e-4     # f32: max|y - ref| / max|ref| over the 8 outputs
BACKEND_RTOL = 2e-2   # default precision: pallas vs xla training loss, per step


FAILURES: list[str] = []


def check(ok: bool, what: str) -> None:
    """Record a failed check.  Later phases still run, so one run on the
    chip reports every failure; ``main`` exits nonzero if any was recorded."""
    if not ok:
        FAILURES.append(what)
        print(f"FAIL: {what}", flush=True)


def phase(name: str, fn, *args):
    """Run one phase; an exception in it is a recorded failure, not the end
    of the run.  Returns the phase's result, or None if it raised."""
    try:
        return fn(*args)
    except Exception:  # noqa: BLE001 - reported, counted, and the run goes on
        traceback.print_exc()
        check(False, f"phase {name!r} raised")
        return None


def rel(a, b) -> float:
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(float(np.max(np.abs(b))), 1e-30))


def grad_rel(ga, gb) -> tuple[float, str, float]:
    """The worst leaf by relative L2 error ``|g - ref| / |ref|``, its key
    path, and the worst leaf by ``rel`` (max-element), for information."""
    import jax
    import numpy as np

    worst, worst_elem = (0.0, ""), 0.0
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(ga)[0], jax.tree.leaves(gb)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        l2 = float(np.linalg.norm(a - b) / max(float(np.linalg.norm(b)), 1e-30))
        worst = max(worst, (l2, jax.tree_util.keystr(path)))
        worst_elem = max(worst_elem, rel(a, b))
    return worst[0], worst[1], worst_elem


def train(backend: str, grid: int, ckpt_root: Path):
    """3 SGD steps through the launcher; the driver's report is checked."""
    from repro.launch.train import parse_args, run_tiled

    ckpt = tempfile.mkdtemp(prefix=f"{backend}_{grid}x{grid}_", dir=ckpt_root)
    args = parse_args([
        "--arch", "yolov2-tiled", "--input-hw", str(HW), "--depth", str(DEPTH),
        "--batch", str(BATCH), "--steps", str(STEPS), "--optimizer", "sgd",
        "--lr", "0.01", "--grid", str(grid), "--backend", backend,
        "--schedule", "sync", "--seed", str(SEED), "--ckpt-dir", ckpt,
        "--resume", "never", "--log-every", "0",
    ])
    t0 = time.perf_counter()
    run = run_tiled(args)
    rep = run.report
    print(f"[{backend} {grid}x{grid}] wall {time.perf_counter() - t0:.1f}s "
          f"(first step includes compile) step_times_s={rep.step_times} "
          f"losses={rep.losses}")
    check(rep.restarts == 0, f"{backend}: {rep.restarts} restarts")
    check(not rep.hung, f"{backend}: watchdog reported a hang")
    check(rep.steps_done == STEPS and len(rep.losses) == STEPS,
          f"{backend}: {rep.steps_done} of {STEPS} steps")
    check(all(math.isfinite(v) for v in rep.losses), f"{backend}: non-finite loss")
    return run


def compare_reference(runs, ref_device, show_default: bool) -> float:
    """First-step loss and grads of each run's tiled grad step against the
    untiled reference on ``ref_device``, both under fp32 matmul precision;
    with ``show_default``, the first run's differences at default precision
    are printed too.  Returns the fp32 reference loss."""
    import jax

    from repro.core.fusion import make_deferred_grad_step, reference_loss

    first = runs[0]
    plan, loss_local = first.arch.plan, first.arch.loss_local
    params = first.init_state(jax.random.PRNGKey(SEED)).params
    batch = first.make_batch(0)
    ref_fn = jax.jit(jax.value_and_grad(
        lambda p, x, t: reference_loss(p, x, t, plan, loss_local)
    ))
    ref_in = jax.device_put((params, batch["x"], batch["t"]), ref_device)
    steps = [
        (run, jax.jit(make_deferred_grad_step(
            run.arch.plan, run.arch.mesh, run.arch.loss_local,
            row_axis=run.arch.row_axis, col_axis=run.arch.col_axis, microbatches=1,
        )))
        for run in runs
    ]
    out = {}
    for prec, todo in (("float32", steps), ("default", steps[:1] if show_default else [])):
        if not todo:
            continue
        with jax.default_matmul_precision(None if prec == "default" else prec):
            l_ref, g_ref = ref_fn(*ref_in)
            for run, step in todo:
                l_t, g_t = step(params, batch["x"][None], batch["t"][None])
                dl = rel(l_t, l_ref)
                dg, leaf, dg_elem = grad_rel(g_t, g_ref)
                p = run.arch.plan
                print(f"[reference {prec}] {p.backend} {p.n}x{p.m}: "
                      f"loss={float(l_t)!r} ref={float(l_ref)!r} "
                      f"loss_rel={dl!r} grad_rel={dg!r} (leaf {leaf}) "
                      f"grad_max_elem_rel={dg_elem!r}")
                if prec == "float32":
                    check(dl <= LOSS_RTOL, f"{p.backend}: loss_rel {dl} > {LOSS_RTOL}")
                    check(dg <= GRAD_RTOL, f"{p.backend}: grad_rel {dg} > {GRAD_RTOL}")
        out[prec] = float(l_ref)
    return out["float32"]


def serve(run):
    """Freeze BN on a calibration batch, serve 8 requests on the 1x1 grid,
    and check the outputs against the untiled forward (fp32 both)."""
    import jax
    import numpy as np

    from repro.core.fusion import reference_forward
    from repro.runtime.driver import run_serving

    arch = run.arch
    params = jax.device_get(run.report.final_state.params)
    rng = np.random.default_rng(SEED + 1)
    calib = rng.standard_normal((BATCH, HW, HW, 3)).astype(np.float32)
    images = rng.standard_normal((BATCH, HW, HW, 3)).astype(np.float32)
    with jax.default_matmul_precision("float32"):
        engine = arch.make_serve_engine(
            params, calibration=calib, buckets=(BATCH,), latency_budget=600.0
        )
        t0 = time.perf_counter()
        engine.warmup()
        compile_s = time.perf_counter() - t0

        def submit_all(t, eng):
            if t == 0:
                for img in images:
                    eng.submit(img)

        rep = run_serving(engine, ticks=1, on_tick=submit_all)
        got = np.stack([r.result for r in sorted(engine.finished, key=lambda r: r.rid)])
        want = np.asarray(jax.jit(
            lambda p, x: reference_forward(p, x, engine.plan)
        )(engine.params, images))
    d = rel(got, want)
    print(f"[serve 1x1] compile {compile_s:.1f}s served={rep.served} "
          f"dispatches={rep.dispatches} p50_s={rep.p50_s!r} out={got.shape} "
          f"rel={d!r}")
    check(rep.served == BATCH, f"served {rep.served} of {BATCH}")
    check(bool(np.all(np.isfinite(got))), "non-finite serve output")
    check(d <= SERVE_RTOL, f"serve rel {d} > {SERVE_RTOL}")


def check_kernels(run) -> None:
    """The compiled pallas train step calls the Mosaic kernels."""
    import jax

    abstract = jax.eval_shape(run.init_state, jax.random.PRNGKey(SEED))
    hlo = run.step_fn.lower(abstract, run.make_batch(0)).compile().as_text()
    n_kernels = hlo.count("tpu_custom_call")
    print(f"[pallas 1x1] compiled step holds {n_kernels} tpu_custom_call sites")
    check(n_kernels > 0, "pallas step has no tpu_custom_call")


def compare_backends(runs) -> None:
    for i, (a, b) in enumerate(zip(runs["pallas"].report.losses, runs["xla"].report.losses)):
        d = abs(a - b) / abs(b)
        print(f"[backends] step {i}: pallas={a!r} xla={b!r} rel={d!r}")
        check(d <= BACKEND_RTOL, f"step {i}: pallas vs xla rel {d} > {BACKEND_RTOL}")


def four_chip_phase(ckpt_root: Path, ref_device) -> None:
    run = train("xla", 2, ckpt_root)
    state_devs = on_devices(run.report.final_state)
    batch_devs = on_devices(run.make_batch(0))
    print(f"[xla 2x2] state on {len(state_devs)} devices, "
          f"batch on {len(batch_devs)} devices")
    check(len(state_devs) == 4 and len(batch_devs) == 4,
          "2x2 step inputs/state not spread over 4 devices")
    l_ref = compare_reference([run], ref_device, show_default=False)
    print(f"[xla 2x2] first training step at default precision: "
          f"loss={run.report.losses[0]!r} vs fp32 reference {l_ref!r}")


def smoke(four_chips: bool) -> None:
    """Every phase of one invocation; failures land in ``FAILURES``."""
    import jax

    devices = jax.devices()
    with tempfile.TemporaryDirectory(prefix=".smoke_ckpt_", dir=ROOT) as tmp:
        ckpt_root = Path(tmp)
        if four_chips:
            phase("train xla 2x2", four_chip_phase, ckpt_root, devices[0])
            return
        runs = {}
        for be in ("xla", "pallas"):
            run = phase(f"train {be} 1x1", train, be, 1, ckpt_root)
            if run is not None:
                runs[be] = run
        if "pallas" in runs:
            phase("pallas kernels", check_kernels, runs["pallas"])
        if len(runs) == 2:
            phase("backends", compare_backends, runs)
        if runs:
            phase("reference", compare_reference, list(runs.values()), devices[0], True)
        if "xla" in runs:
            phase("serve", serve, runs["xla"])


def on_devices(tree) -> set:
    import jax

    return set().union(*(leaf.sharding.device_set for leaf in jax.tree.leaves(tree)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 tile grid over four chips vs the 1x1 reference")
    args = ap.parse_args(argv)
    sys.stdout.reconfigure(line_buffering=True)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, found {len(devices)}", file=sys.stderr)
        return 2

    from repro.compat import enable_compile_cache

    print(f"device: {dev.device_kind} x{len(devices)}; "
          f"compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    smoke(args.four_chips)
    stats = dev.memory_stats() or {}
    print(f"total {time.perf_counter() - t0:.1f}s; peak_bytes_in_use(device 0)="
          f"{stats.get('peak_bytes_in_use')}")
    if FAILURES:
        print(f"chip_smoke: {len(FAILURES)} check(s) failed: {FAILURES}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
