"""Readings from which a training cell's limits are set, on the chip.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3,4 [--control 3]

For each of ``--seeds`` the program runs its set-up and checked steps, a
two-step window and the reference, as a benchmark run does, and its
numbers are printed: the lower readings. On the first ``--control`` seeds the
reference with its network in the cell's ``control`` numerics (the precision
below the one the configuration states; parameters, momentum and the loss's
sum stay float32) is put in the program's place, and so are the faults
planted in the reference: half of the batch left out with the mean over the
rest, and, on a tile grid, each tile convolved alone with the halo exchange
left out. Their numbers are the upper readings. A state left unchanged reads 1
on ``update_gap`` by definition and needs no run. Each record also holds
every leaf's norms, so that another way of comparing them can be read after
the call. Every reading goes to ``bench_out/calibrate.<name>.jsonl`` in the
checkout too.

The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import run_cell


def _seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--precision", default=None,
                    help="run the program at this matmul precision (a witness)")
    ap.add_argument("--control", type=int, default=0,
                    help="read the control and the faults on the first N seeds")
    args = ap.parse_args(argv)
    entry, cell, cfg = run_cell.load_cell(args.workload)
    devices = run_cell.init_jax(entry["chips"], require_tpu=True)
    sys.path.insert(0, str(run_cell.ROOT / "src"))
    import jax

    import checks
    import reference
    import train_job

    out_dir = run_cell.ROOT / "bench_out"
    out_dir.mkdir(exist_ok=True)
    log = open(out_dir / f"calibrate.{args.workload}.jsonl", "a")

    def emit(rec: dict) -> None:
        line = json.dumps(rec)
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()

    layers = reference.layers_from_config(cfg)
    label = "program"
    if args.precision:
        # a witness: the program with its convolutions at another precision
        jax.config.update("jax_default_matmul_precision", args.precision)
        label = f"program@{args.precision}"
    prog = train_job.build_program(cfg, cell, layers)
    ctrl = reference.Numerics(**cell["control"])
    for i, seed in enumerate(_seeds(args.seeds)):
        t0 = time.perf_counter()
        ctx = run_cell.Context(args.workload, cell, cfg, seed, 0.0, False, devices)
        res = train_job.run(ctx, prog)
        emit({"kind": label, "seed": seed, "sound": res["sound"], **res["values"],
              "losses": res["losses"], "ref_losses": res["ref_losses"],
              **norms(res["prog_traj"], "got"), **norms(res["ref_traj"], "ref"),
              "peak": res["memory_peak"], "memory_stats": res["memory_stats"],
              "phases": res["phases"], "s": time.perf_counter() - t0})
        if i >= args.control:
            continue
        pool, p0, want = res["pool"], res["p0"], res["ref_traj"]
        del res
        n, micro = train_job.CHECK_STEPS, cell["grad_accum"]
        faults = {"control": dict(num=ctrl), "half_batch": dict(rows=cell["batch"] // 2)}
        if tuple(cell["grid"]) != (1, 1):
            faults["no_exchange"] = dict(tiles=tuple(cell["grid"]))
        for kind, kw in faults.items():
            t0 = time.perf_counter()
            got = train_job.follow_reference(cfg, devices, pool, p0, n, micro, **kw)
            emit({"kind": kind, "seed": seed, **checks.readings(got, want),
                  "losses": got.losses, "ref_losses": want.losses, **norms(got, "got"),
                  "s": time.perf_counter() - t0})
    log.close()
    return 0


def norms(traj, side: str) -> dict:
    """Each leaf's norm of the first gradient and of the change, so that a
    number can be looked at leaf by leaf after the call."""
    import checks

    return {f"{side}_grad_norm": checks.leaf_norms(traj.grad),
            f"{side}_delta_norm": checks.leaf_norms(traj.delta)}


if __name__ == "__main__":
    raise SystemExit(main())
