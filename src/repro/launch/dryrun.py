import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count on first init.
# The dry-run (and ONLY the dry-run) builds the production meshes out of 512
# placeholder host devices; smoke tests / benches see the real 1-CPU world.

"""Multi-pod dry-run: lower + compile every (architecture x shape x mesh)
cell, extract memory/cost/collective analysis, and emit one JSON artifact
per cell for the roofline table (EXPERIMENTS.md S Dry-run / S Roofline).

Methodology (see analysis/roofline.py): ``cost_analysis`` counts a
``lax.scan`` body once, so per-cell FLOP/byte/collective totals are measured
from two shallow UNROLLED lowerings (depths p and 2p periods) and scaled to
the full depth; the full-depth compile proves the sharding + memory fit and
supplies the collective schedule.  Decode steps have no layer scan and are
measured directly at full depth.

Usage:
  python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --outdir artifacts/dryrun
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.hlo import collective_stats
from repro.compat import enable_compile_cache
from repro.analysis.roofline import (
    V5E,
    count_params_cfg,
    embed_param_count,
    flash_attention_terms,
    fmt_bytes,
    fmt_seconds,
    model_flops,
    terms_from_counts,
)
from repro.models.attention import attention_impl
from repro.configs.base import ParallelConfig, ShapeConfig, TrainConfig, SHAPES
from repro.launch.mesh import make_production_mesh
from repro.models import lm as lm_mod
from repro.models import whisper as whisper_mod
from repro.models.registry import (
    ARCH_IDS,
    bundle_from_cfg,
    cell_supported,
    load_config,
    period_counts,
    with_depth,
)
from repro.parallel.api import layout_rules, sharding_ctx
from repro.parallel.sharding import (
    batch_shardings,
    cache_shardings,
    param_shardings,
    state_shardings,
)
from repro.train.trainer import abstract_state, make_train_step


# ---------------------------------------------------------------------------
# Per-kind lowering
# ---------------------------------------------------------------------------


def _pick_optimizer(cfg) -> str:
    """adafactor above ~100B params (factored moments; HBM fit), else adamw."""
    bundle = bundle_from_cfg(cfg)
    total, _ = count_params_cfg(bundle.abstract_params(), cfg)
    return "adafactor" if total > 1e11 else "adamw"


def _replicated(mesh):
    return NamedSharding(mesh, P())


def lower_train(cfg, shape: ShapeConfig, mesh, *, unroll: bool, pcfg=None,
                layout: str = "tp-sp"):
    bundle = bundle_from_cfg(cfg)
    pcfg = pcfg or ParallelConfig(unroll=unroll, remat="full", layout=layout)
    if unroll and not pcfg.unroll:
        pcfg = dataclasses.replace(pcfg, unroll=True)
    tcfg = TrainConfig(optimizer=_pick_optimizer(cfg))
    init_state, train_step = make_train_step(bundle, pcfg, tcfg)
    state_abs = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    batch_abs = bundle.input_specs(shape)
    with sharding_ctx(mesh, rules=layout_rules(pcfg.layout)):
        p_sh = param_shardings(state_abs.params)
        o_sh = state_shardings(state_abs.opt, state_abs.params)
        state_sh = type(state_abs)(p_sh, o_sh, _replicated(mesh), None)
        b_sh = batch_shardings(batch_abs)
        m_sh = {"loss": _replicated(mesh), "grad_norm": _replicated(mesh), "lr": _replicated(mesh)}
        lowered = jax.jit(
            train_step, in_shardings=(state_sh, b_sh), out_shardings=(state_sh, m_sh),
            donate_argnums=(0,),   # state aliases in->out (no double residency)
        ).lower(state_abs, batch_abs)
    return lowered


def lower_prefill(cfg, shape: ShapeConfig, mesh, *, unroll: bool,
                  layout: str = "tp-sp"):
    bundle = bundle_from_cfg(cfg)
    batch_abs = bundle.input_specs(shape)

    if cfg.encoder_decoder:
        def prefill_step(params, batch):
            enc = whisper_mod.encode(params, batch["frames"], cfg, remat="none", unroll=unroll)
            hidden = whisper_mod.decode_train(
                params, batch["tokens"], enc, cfg, remat="none", unroll=unroll
            )
            head = params["embed"].astype(cfg.act_dtype)
            return (hidden[:, -1:] @ head.T).astype(jnp.float32)
    else:
        def prefill_step(params, batch):
            hidden = lm_mod.apply_lm(
                params,
                batch["tokens"],
                cfg,
                positions=batch.get("positions"),
                extra_embeds=batch.get("patch_embeds"),
                remat="none",
                unroll=unroll,
            )
            head = lm_mod.lm_head_weight(params, cfg).astype(cfg.act_dtype)
            return (hidden[:, -1:] @ head.T).astype(jnp.float32)

    params_abs = bundle.abstract_params()
    with sharding_ctx(mesh, rules=layout_rules(layout)):
        p_sh = param_shardings(params_abs)
        b_sh = batch_shardings(batch_abs)
        lowered = jax.jit(prefill_step, in_shardings=(p_sh, b_sh)).lower(
            params_abs, batch_abs
        )
    return lowered


def lower_decode(cfg, shape: ShapeConfig, mesh, *, seq_sharded: bool,
                 layout: str = "tp-sp"):
    bundle = bundle_from_cfg(cfg)
    b, s = shape.global_batch, shape.seq_len
    params_abs = bundle.abstract_params()
    caches_abs = bundle.cache_specs(b, s)
    token_abs = jax.ShapeDtypeStruct((b, 1), jnp.int32)

    if cfg.encoder_decoder:
        def serve_step(params, token, caches):
            return whisper_mod.whisper_decode_step(params, token, caches, cfg)
    else:
        def serve_step(params, token, caches):
            return lm_mod.decode_step(
                params, token, caches, cfg, seq_sharded_cache=seq_sharded
            )

    with sharding_ctx(mesh, rules=layout_rules(layout)):
        p_sh = param_shardings(params_abs)
        c_sh = cache_shardings(caches_abs, seq_sharded=seq_sharded)
        t_sh = batch_shardings({"token": token_abs})["token"]
        lowered = jax.jit(
            serve_step,
            in_shardings=(p_sh, t_sh, c_sh),
            out_shardings=(None, c_sh),
            donate_argnums=(2,),   # KV/SSM caches alias in->out
        ).lower(params_abs, token_abs, caches_abs)
    return lowered


# ---------------------------------------------------------------------------
# Cell analysis
# ---------------------------------------------------------------------------


def _compile_stats(lowered):
    t0 = time.monotonic()
    compiled = lowered.compile()
    dt = time.monotonic() - t0
    cost = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    txt = compiled.as_text()
    coll = collective_stats(txt)
    return {
        "compile_s": round(dt, 2),
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll_ring": coll.total_ring,
        "coll_naive": coll.total_naive,
        "coll_count": coll.count,
        "mem": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "total_bytes": mem.argument_size_in_bytes
            + mem.output_size_in_bytes
            + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes,
        },
    }


def run_cell(
    arch: str,
    shape_name: str,
    mesh_kind: str,
    *,
    rates: bool = True,
    seq_shard_long: bool = True,
    pcfg: ParallelConfig | None = None,
    layout: str = "tp-sp",
    cfg_transform=None,
) -> dict:
    cfg = load_config(arch)
    if cfg_transform is not None:
        cfg = cfg_transform(cfg)
    shape = SHAPES[shape_name]
    ok, why = cell_supported(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind, "skipped": why}
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = mesh.size
    out: dict = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_kind,
        "kind": shape.kind,
        "devices": n_dev,
        "layout": layout,
    }

    bundle = bundle_from_cfg(cfg)
    total, active = count_params_cfg(bundle.abstract_params(), cfg)
    out["params_total"] = total
    out["params_active"] = active

    def lower_full():
        if shape.kind == "train":
            return lower_train(cfg, shape, mesh, unroll=False, pcfg=pcfg, layout=layout)
        if shape.kind == "prefill":
            return lower_prefill(cfg, shape, mesh, unroll=False, layout=layout)
        # 32k+ caches shard over *sequence* (flash-decode): KV-head counts
        # need not divide TP, and the cache is the decode working set
        return lower_decode(
            cfg, shape, mesh,
            seq_sharded=(shape.seq_len >= 32_768 and seq_shard_long),
            layout=layout,
        )

    # ---- full-depth compile: sharding validity + memory fit + schedule ----
    # blocked (flash-style streaming) attention: the memory-honest XLA
    # expression of what the Pallas kernel does on TPU
    with attention_impl("blocked"):
        full = _compile_stats(lower_full())
    out["full"] = full

    # ---- flop/byte/collective totals ----
    if shape.kind == "decode" or not rates:
        # decode has no layer scan: full-depth numbers are already exact
        flops, bytes_hbm, ring, naive = (
            full["flops"], full["bytes"], full["coll_ring"], full["coll_naive"]
        )
        out["rates"] = {"method": "direct"}
    else:
        # shallow UNROLLED counting lowerings with stubbed attention;
        # flash-kernel analytic terms added back below
        prefix, reps = period_counts(cfg)
        d1, d2 = with_depth(cfg, 1), with_depth(cfg, 2)
        # (cfg already carries any cfg_transform; with_depth preserves it)
        with attention_impl("stub"):
            if shape.kind == "train":
                lw = lambda c, *a, **kw: lower_train(c, *a, pcfg=pcfg, **kw)
            else:
                lw = lower_prefill
            s1 = _compile_stats(lw(d1, shape, mesh, unroll=True, layout=layout))
            s2 = _compile_stats(lw(d2, shape, mesh, unroll=True, layout=layout))

        def scale(k):
            per = s2[k] - s1[k]
            return s1[k] + (reps - 1) * per

        flops, bytes_hbm = scale("flops"), scale("bytes")
        ring, naive = scale("coll_ring"), scale("coll_naive")
        fa_fl, fa_by = flash_attention_terms(
            cfg, shape, remat=(shape.kind == "train")
        )
        flops += fa_fl / n_dev
        bytes_hbm += fa_by / n_dev
        out["rates"] = {
            "method": "unrolled-diff+flash-analytic",
            "prefix_layers": prefix,
            "periods": reps,
            "flash_flops_global": fa_fl,
            "flash_bytes_global": fa_by,
            "d1": {k: s1[k] for k in ("flops", "bytes", "coll_ring", "compile_s")},
            "d2": {k: s2[k] for k in ("flops", "bytes", "coll_ring", "compile_s")},
        }

    out["hbm_ok"] = bool(full["mem"]["total_bytes"] <= V5E.hbm_bytes)
    if mesh_kind != "single" and shape.kind != "decode" and rates is False:
        # multi-pod pass proves sharding + memory fit only; the roofline
        # table is single-pod (scan bodies are counted once in `full`, so
        # term math would be misleading here)
        out["roofline"] = "n/a (multi-pod compile-proof cell)"
        return out
    terms = terms_from_counts(flops, bytes_hbm, ring)
    mf = model_flops(cfg, shape, active, embed_params=embed_param_count(cfg))
    out.update(
        flops_per_device=flops,
        bytes_per_device=bytes_hbm,
        coll_ring_per_device=ring,
        coll_naive_per_device=naive,
        compute_s=terms.compute_s,
        memory_s=terms.memory_s,
        collective_s=terms.collective_s,
        dominant=terms.dominant,
        bound_s=terms.bound_s,
        model_flops_global=mf,
        model_flops_per_device=mf / n_dev,
        useful_ratio=(mf / n_dev) / flops if flops else 0.0,
        roofline_fraction=(mf / n_dev / V5E.peak_flops) / terms.bound_s
        if terms.bound_s
        else 0.0,
    )
    return out


def summarize(res: dict) -> str:
    if "skipped" in res:
        return f"[{res['arch']} x {res['shape']} @ {res['mesh']}] SKIP: {res['skipped']}"
    if "compute_s" not in res:
        return (
            f"[{res['arch']} x {res['shape']} @ {res['mesh']}] COMPILE OK "
            f"mem/dev={fmt_bytes(res['full']['mem']['total_bytes'])} "
            f"(fits={res['hbm_ok']})"
        )
    return (
        f"[{res['arch']} x {res['shape']} @ {res['mesh']}] "
        f"compute={fmt_seconds(res['compute_s'])} "
        f"memory={fmt_seconds(res['memory_s'])} "
        f"collective={fmt_seconds(res['collective_s'])} "
        f"dominant={res['dominant']} "
        f"roofline={res['roofline_fraction']:.1%} "
        f"mem/dev={fmt_bytes(res['full']['mem']['total_bytes'])} "
        f"(fits={res['hbm_ok']})"
    )


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--all", action="store_true", help="run every supported cell")
    ap.add_argument("--outdir", default="artifacts/dryrun")
    ap.add_argument("--no-rates", action="store_true", help="skip shallow rate compiles")
    ap.add_argument("--layout", default="tp-sp", help="parallelism layout (see parallel.api.LAYOUTS)")
    ap.add_argument("--remat", default="full", choices=["none", "full", "dots"])
    ap.add_argument("--ssm-chunk", type=int, default=0, help="override SSD chunk size")
    ap.add_argument("--suffix", default="", help="artifact filename suffix (layout experiments)")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    cells = []
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        for arch in ARCH_IDS:
            for shape in SHAPES:
                for mk in meshes:
                    cells.append((arch, shape, mk))
    else:
        if not (args.arch and args.shape):
            ap.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape, mk) for mk in meshes]

    if args.list:
        for c in cells:
            print(*c)
        return 0

    os.makedirs(args.outdir, exist_ok=True)
    failures = 0
    for arch, shape, mk in cells:
        name = f"{arch}__{shape}__{mk}{args.suffix}.json".replace("/", "_")
        path = os.path.join(args.outdir, name)
        try:
            # rates only needed for the single-pod roofline table
            res = run_cell(
                arch, shape, mk,
                rates=(mk == "single" and not args.no_rates),
                layout=args.layout,
                pcfg=ParallelConfig(remat=args.remat, layout=args.layout),
                cfg_transform=(
                    (lambda c: dataclasses.replace(
                        c, ssm=dataclasses.replace(c.ssm, chunk=args.ssm_chunk)))
                    if args.ssm_chunk and True else None
                ),
            )
            print(summarize(res), flush=True)
        except Exception as e:  # noqa: BLE001
            failures += 1
            res = {
                "arch": arch, "shape": shape, "mesh": mk,
                "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-4000:],
            }
            print(f"[{arch} x {shape} @ {mk}] FAIL: {res['error']}", flush=True)
        with open(path, "w") as f:
            json.dump(res, f, indent=1)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
