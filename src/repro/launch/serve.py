"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Two modes:

- LM (default): slot-based continuous-batching engine on a reduced config;
  pushes a synthetic request workload (prompt/output lengths drawn
  deterministically) and prints per-request outputs + throughput.
- ``--cnn``: tiled-CNN inference serving (DESIGN.md §13) - builds a
  YOLOv2-prefix plan over an n x m tile grid, takes its forward-only twin,
  freezes BN statistics on a calibration batch, warms the executable cache
  over the bucket ladder, then drives a synthetic image workload through
  ``runtime.driver.run_serving`` and prints latency percentiles,
  throughput, bucket census and cache hit rate.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.compat import enable_compile_cache
from repro.models.registry import ARCH_IDS, get_arch
from repro.serve.engine import Request, ServeEngine

import jax


def _lm_main(args) -> int:
    arch = get_arch(args.arch, reduced=True)
    params = arch.init(jax.random.PRNGKey(args.seed))
    engine = ServeEngine(
        arch, params, batch=args.slots, max_seq=args.max_seq,
        temperature=args.temperature,
    )
    rng = np.random.default_rng(args.seed)
    for rid in range(args.requests):
        plen = int(rng.integers(4, 12))
        prompt = rng.integers(0, arch.cfg.vocab, size=plen).astype(np.int32)
        engine.submit(Request(rid=rid, prompt=prompt, max_new_tokens=args.max_new))

    t0 = time.monotonic()
    done = engine.run(max_ticks=args.requests * (args.max_new + 16))
    dt = time.monotonic() - t0
    toks = sum(len(r.out_tokens) for r in done)
    for r in sorted(done, key=lambda r: r.rid):
        print(f"req {r.rid}: prompt_len={len(r.prompt)} out={r.out_tokens[:8]}...")
    print(f"served {len(done)} requests, {toks} tokens in {dt:.2f}s ({toks/dt:.1f} tok/s)")
    return 0


def _cnn_main(args) -> int:
    from repro.models.yolo import make_yolo_tiled_arch
    from repro.runtime.driver import run_serving

    n, m = (int(v) for v in args.grid.split("x"))
    arch = make_yolo_tiled_arch(
        input_hw=(args.size, args.size), depth=args.depth, n=n, m=m,
        groups="auto" if args.groups == "auto" else None,
        backend=args.backend, schedule=args.schedule, hw=args.hw,
        batch=max(args.buckets), crossover=args.crossover,
        wire_codec=args.wire_codec,
    )
    params = arch.init(jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)
    h, w = arch.plan.input_hw
    cin = arch.plan.layers[0].in_channels
    calib = rng.standard_normal((max(args.buckets), h, w, cin)).astype(np.float32)
    engine = arch.make_serve_engine(
        params, calibration=calib,
        buckets=tuple(args.buckets),
        latency_budget=args.budget_ms / 1e3,
        hw=args.hw,
    )
    t0 = time.monotonic()
    engine.warmup()
    print(f"warmup: {len(engine.buckets)} buckets compiled in "
          f"{time.monotonic() - t0:.2f}s "
          f"(cache: {engine.cache.stats()})")

    per_tick = max(1, args.requests // max(1, args.ticks))

    def on_tick(t, eng):
        for _ in range(per_tick):
            if eng._rid < args.requests:
                eng.submit(
                    rng.standard_normal((h, w, cin)).astype(np.float32)
                )

    t0 = time.monotonic()
    report = run_serving(engine, ticks=args.ticks, on_tick=on_tick)
    dt = time.monotonic() - t0
    print(f"served {report.served} requests in {dt:.2f}s "
          f"over {report.dispatches} dispatches")
    if report.p50_s is not None:
        print(f"latency p50={report.p50_s*1e3:.1f}ms p99={report.p99_s*1e3:.1f}ms "
              f"throughput={report.throughput:.1f} img/s")
    print(f"bucket census: {report.bucket_census}  "
          f"deadline misses: {report.deadline_misses}  "
          f"min slack: {report.min_slack_s:+.3f}s")
    print(f"cache: {report.cache}")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--cnn", action="store_true",
                    help="tiled-CNN image serving instead of the LM engine")
    ap.add_argument("--arch", choices=ARCH_IDS, default="stablelm-1.6b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    # --cnn mode
    ap.add_argument("--grid", default="1x1", help="tile grid n x m (n*m devices)")
    ap.add_argument("--depth", type=int, default=6, help="YOLOv2 prefix depth")
    ap.add_argument("--size", type=int, default=64, help="input H=W")
    ap.add_argument("--backend", choices=("xla", "pallas"), default="xla")
    ap.add_argument("--schedule", choices=("sync", "overlap"), default="sync")
    ap.add_argument("--groups", choices=("none", "auto"), default="none")
    ap.add_argument("--crossover", default=None,
                    help="spatial->data crossover layer or 'auto'")
    ap.add_argument("--wire-codec", default="none")
    ap.add_argument("--hw", default=None, help="hardware profile name")
    ap.add_argument("--buckets", type=int, nargs="+", default=[1, 2, 4, 8])
    ap.add_argument("--budget-ms", type=float, default=1000.0)
    ap.add_argument("--ticks", type=int, default=16)
    args = ap.parse_args()
    enable_compile_cache()
    if args.crossover is not None and args.crossover != "auto":
        args.crossover = int(args.crossover)
    return _cnn_main(args) if args.cnn else _lm_main(args)


if __name__ == "__main__":
    raise SystemExit(main())
