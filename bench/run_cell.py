"""Run one cell of the benchmark on the chip and print its result line.

    python3 bench/run_cell.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell is found by name: its entry in
``BENCHMARK.json``, its file ``bench/cells/<name>.json`` and its configuration
file. The cell's ``job`` names the module ``bench/<job>_job.py`` that runs it.
With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the result carries the
cell's per-layer metrics, each read by ``bench/layer_metrics/<metric>.py``.

The last lines on standard error, and the result's last key ``checks``, give
each number compared with the reference beside its limit. The last line on
standard output is the result, one JSON object. Without a TPU, or with fewer
chips than the cell asks for, the run exits nonzero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import contextlib
import dataclasses
import importlib
import json
import os
import sys
import tempfile
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
COMPILE_CACHE = ROOT / ".jax_cache"
sys.path.insert(0, str(BENCH))


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def entry_of(benchmark: dict, name: str) -> dict:
    """The cell's ``workloads`` entry: its configuration and its chips."""
    entries = {w["name"]: w for w in benchmark["workloads"]}
    if name not in entries:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    return entries[name]


def load_cell(name: str, benchmark: dict | None = None) -> tuple[dict, dict, dict]:
    """The cell's ``BENCHMARK.json`` entry, its cell file and its configuration."""
    benchmark = benchmark or load_json(ROOT / "BENCHMARK.json")
    entry = entry_of(benchmark, name)
    cell = load_json(BENCH / "cells" / f"{name}.json")
    configs = {c["name"]: c for c in benchmark["configs"]}
    cfg = load_json(ROOT / configs[entry["config"]]["file"])
    return entry, cell, cfg


def metrics_for(benchmark: dict, name: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics that this cell reports."""
    return [m for m in benchmark[kind] if name in m.get("workloads", [name])]


@dataclasses.dataclass
class Context:
    """What a job needs from the harness."""

    name: str
    cell: dict
    cfg: dict
    seed: int
    seconds: float
    trace: bool
    devices: list
    trace_dir: str | None = None

    @contextlib.contextmanager
    def tracer(self):
        import jax

        if not self.trace:
            yield
            return
        jax.profiler.start_trace(self.trace_dir)
        try:
            yield
        finally:
            jax.profiler.stop_trace()

    def memory_peak(self) -> int:
        """Peak bytes on the fullest chip: buffers in use plus the space the
        TPU runtime reserves for compiled programs' temporaries, which
        ``peak_bytes_in_use`` leaves out."""
        stats = [d.memory_stats() or {} for d in self.devices]
        return max(s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0) for s in stats)


def init_jax(chips: int, require_tpu: bool):
    """JAX with the compile cache in the checkout; the devices the cell uses.
    Exits 2 without a TPU or with fewer chips than the cell asks for."""
    import jax

    COMPILE_CACHE.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        print(f"run_cell: needs a TPU, JAX found {devices[0].platform!r}", file=sys.stderr)
        raise SystemExit(2)
    if len(devices) < chips:
        print(f"run_cell: needs {chips} chips, found {len(devices)}", file=sys.stderr)
        raise SystemExit(2)
    return devices[:chips]


def run(name: str, seed: int, seconds: float, trace: bool, *, benchmark: dict | None = None,
        cell: dict | None = None, cfg: dict | None = None, require_tpu: bool = True) -> dict:
    """One run of a cell; returns the result object. Tests pass ``benchmark``,
    ``cell`` and ``cfg`` of their own and ``require_tpu=False``."""
    benchmark = benchmark or load_json(ROOT / "BENCHMARK.json")
    if cell is None:
        _, cell, cfg = load_cell(name, benchmark)
    devices = init_jax(entry_of(benchmark, name)["chips"], require_tpu)
    sys.path.insert(0, str(ROOT / "src"))
    job = importlib.import_module(f"{cell['job']}_job")
    with tempfile.TemporaryDirectory(prefix="bench_trace_") as trace_dir:
        ctx = Context(name, cell, cfg, seed, seconds, trace, devices, trace_dir)
        result = job.run(ctx)
        layer = read_layer_metrics(benchmark, ctx, result) if trace else None
    return assemble(benchmark, ctx, result, layer)


def read_layer_metrics(benchmark: dict, ctx: Context, result: dict) -> dict:
    """Reduce the trace and ask each per-layer metric's reader for its value."""
    import devtrace
    import work

    summary = devtrace.reduce_dir(ctx.trace_dir, len(ctx.devices), result["window"]["steps"])
    run_info = LayerRun(ctx, result, summary, work.peaks_for(ctx.devices[0].device_kind))
    values = {}
    for m in metrics_for(benchmark, ctx.name, "per_layer"):
        reader = importlib.import_module(f"layer_metrics.{m['name']}")
        v = reader.read(run_info)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    return {"metrics": values, "summary": summary}


@dataclasses.dataclass
class LayerRun:
    """What a per-layer metric's reader sees of a traced run."""

    ctx: Context
    result: dict
    trace: object               # devtrace.Summary
    peak: dict                  # the device kind's row of peaks.json


def assemble(benchmark: dict, ctx: Context, result: dict, layer: dict | None) -> dict:
    job = importlib.import_module(f"{ctx.cell['job']}_job")
    checks_mod = importlib.import_module("checks")
    checks = checks_mod.judge(result["values"], ctx.cell["limits"])
    correct = result["sound"] and checks_mod.passed(checks)
    dev = ctx.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(ctx.devices),
              "memory_peak_bytes": result["memory_peak"]}
    out = {"correct": correct, "attempted": result["attempted"], "failed": result["failed"]}
    if layer is None:
        e2e = dict(job.summary(result), setup_s=result["window"]["t_first"] - T_START)
        units = {m["name"]: m["unit"] for m in metrics_for(benchmark, ctx.name, "end_to_end")}
        out["metrics"] = {k: {"value": e2e[k], "unit": u} for k, u in units.items()}
    else:
        s = layer["summary"]
        out["metrics"] = layer["metrics"]
        device.update(busy_s=s.busy_s, window_s=s.window_s)
        out["breakdown"] = s.breakdown()
    out["device"] = device
    out["window"] = {k: result["window"][k] for k in ("steps", "images", "seconds", "compiles", "gc_s")}
    out["phases"] = result["phases"]
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    out = run(args.workload, args.seed, args.seconds, bool(args.trace))
    print(f"correct {out['correct']}", file=sys.stderr)
    for k, c in out["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
