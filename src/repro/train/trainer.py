"""Training step factory: grad accumulation, clipping, LR schedule,
optimizer update, optional int8-EF gradient compression for the DCN hop.

``make_train_step(arch, pcfg, tcfg)`` returns (init_state, step_fn) where
step_fn is pure and jit-able with explicit in/out shardings - the same
callable the dry-run lowers and the runtime driver executes.

Two arch families share one pipeline (DESIGN.md §3):

  - LM/whisper bundles (``models.registry.ArchBundle``): grads come from
    ``jax.value_and_grad`` over ``arch.loss_fn``, with ``pcfg.grad_accum``
    microbatches accumulated in a local scan.  The paper's deferred weight
    aggregation (§4.1) corresponds to that scan: no collective inside the
    loop; XLA places ONE all-reduce after it.
  - Tiled-CNN bundles (``models.tiled_cnn.TiledCNNArch``, kind
    "tiled_cnn"): grads come from ``core.fusion.make_deferred_grad_step``,
    the shard_map'd executor whose microbatch scan accumulates per-tile
    weight-gradient *partial sums* and psums once per batch - the paper's
    schedule, explicit.

Both paths then run the identical trainer tail: optional int8
error-feedback compression, global-norm clipping, cosine/warmup schedule,
optimizer update.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro import obs
from repro.configs.base import ParallelConfig, TrainConfig
from repro.optim import (
    clip_by_global_norm,
    compression,
    cosine_schedule,
    make_optimizer,
)
from repro.optim.compression import compress_with_feedback, init_error


class TrainState(NamedTuple):
    params: Any
    opt: Any
    step: jax.Array
    ef: Optional[Any] = None      # error-feedback buffers (compression)


def _make_init_state(arch, opt, tcfg: TrainConfig):
    def init_state(key) -> TrainState:
        params = arch.init(key)
        ef = init_error(params).error if tcfg.grad_compression == "int8" else None
        return TrainState(params, opt.init(params), jnp.zeros((), jnp.int32), ef)

    return init_state


def _apply_updates(
    state: TrainState, loss, grads, opt, tcfg: TrainConfig
) -> tuple[TrainState, dict]:
    """Shared trainer tail, under the named scope ``optimizer``: EF
    compression -> clip -> schedule -> update."""
    with jax.named_scope(obs.OPTIMIZER):
        ef = state.ef
        if ef is not None:
            grads, st = compress_with_feedback(grads, compression.CompressionState(ef))
            ef = st.error
        grads, gnorm = clip_by_global_norm(grads, tcfg.grad_clip)
        lr = cosine_schedule(state.step, tcfg.warmup, tcfg.steps, tcfg.lr)
        params, opt_state = opt.update(grads, state.opt, state.params, lr)
        new_state = TrainState(params, opt_state, state.step + 1, ef)
    metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr}
    return new_state, metrics


def make_train_step(arch, pcfg: ParallelConfig, tcfg: TrainConfig):
    if getattr(arch, "kind", None) == "tiled_cnn":
        return _make_tiled_cnn_train_step(arch, pcfg, tcfg)
    return _make_lm_train_step(arch, pcfg, tcfg)


# ---------------------------------------------------------------------------
# LM / whisper path (value_and_grad over arch.loss_fn)
# ---------------------------------------------------------------------------


def _make_lm_train_step(arch, pcfg: ParallelConfig, tcfg: TrainConfig):
    opt = make_optimizer(tcfg.optimizer, weight_decay=tcfg.weight_decay)
    init_state = _make_init_state(arch, opt, tcfg)

    def loss_fn(params, batch):
        return arch.loss_fn(
            params, batch, remat=pcfg.remat, unroll=pcfg.unroll, ce_chunk=pcfg.ce_chunk
        )

    def train_step(state: TrainState, batch: dict) -> tuple[TrainState, dict]:
        accum = pcfg.grad_accum
        if accum > 1:
            def split(x):
                return x.reshape((accum, x.shape[0] // accum) + x.shape[1:])

            # positions (3,B,T) splits on dim 1
            def split_batch(b):
                out = {}
                for k, v in b.items():
                    if k == "positions" and v.ndim == 3:
                        out[k] = v.reshape(
                            (v.shape[0], accum, v.shape[1] // accum) + v.shape[2:]
                        ).swapaxes(0, 1)
                    else:
                        out[k] = split(v)
                return out

            mbs = split_batch(batch)

            def body(carry, mb):
                gacc, lacc = carry
                l, g = jax.value_and_grad(loss_fn)(state.params, mb)
                gacc = jax.tree.map(lambda a, b: a + b.astype(a.dtype), gacc, g)
                return (gacc, lacc + l), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params
            )
            (gsum, lsum), _ = lax.scan(body, (zeros, jnp.float32(0)), mbs)
            grads = jax.tree.map(lambda g: g / accum, gsum)
            loss = lsum / accum
        else:
            loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)

        return _apply_updates(state, loss, grads, opt, tcfg)

    return init_state, train_step


# ---------------------------------------------------------------------------
# Tiled-CNN path (deferred per-batch weight aggregation, paper §4.1)
# ---------------------------------------------------------------------------


def _make_tiled_cnn_train_step(arch, pcfg: ParallelConfig, tcfg: TrainConfig):
    from repro.core.fusion import make_deferred_grad_step

    opt = make_optimizer(tcfg.optimizer, weight_decay=tcfg.weight_decay)
    init_state = _make_init_state(arch, opt, tcfg)
    accum = max(pcfg.grad_accum, 1)
    plan = arch.plan
    grad_step = make_deferred_grad_step(
        arch.plan,
        arch.mesh,
        arch.loss_local,
        row_axis=arch.row_axis,
        col_axis=arch.col_axis,
        batch_axis=arch.batch_axis,
        microbatches=accum,
    )

    def train_step(state: TrainState, batch: dict) -> tuple[TrainState, dict]:
        def split(v):
            if v.shape[0] % accum:
                raise ValueError(
                    f"global batch {v.shape[0]} not divisible by "
                    f"grad_accum={accum} (tiled-CNN microbatch split)"
                )
            return v.reshape((accum, v.shape[0] // accum) + v.shape[1:])

        if plan.stages:
            # trainer-vocabulary guard for pipeline plans: each of the
            # grad_accum microbatches streamed through the stages must
            # split over one stage's device subset
            per = (plan.n * plan.m) // len(plan.stages)
            b = batch["x"].shape[0]
            if b % accum or (b // accum) % per:
                raise ValueError(
                    f"pipeline plan with {len(plan.stages)} stages needs "
                    f"the global batch ({b}) divisible by grad_accum "
                    f"({accum}) and the per-microbatch batch by the "
                    f"devices per stage ({per}); adjust --batch/--grad-accum"
                )
        loss, grads = grad_step(state.params, split(batch["x"]), split(batch["t"]))
        return _apply_updates(state, loss, grads, opt, tcfg)

    return init_state, train_step


def abstract_state(arch, pcfg: ParallelConfig, tcfg: TrainConfig):
    """TrainState ShapeDtypeStructs (dry-run: no allocation)."""
    init_state, _ = make_train_step(arch, pcfg, tcfg)
    return jax.eval_shape(init_state, jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# Elastic replan support (DESIGN.md §10)
# ---------------------------------------------------------------------------


def globalize_state(state: TrainState) -> TrainState:
    """Pull a live TrainState to host as plain numpy - the
    partition-independent form.  Params (and hence every optimizer
    statistic, which mirrors param structure) are replicated across the
    tile mesh, so each leaf is already a full global array; this just
    detaches it from the old mesh's device placement.  The result feeds a
    train step jit'd for a *different* ClusterSpec/TilePartition without
    resharding and without touching optimizer statistics."""
    import numpy as np

    return jax.tree.map(np.asarray, state)


def check_state_matches(state: TrainState, like: TrainState) -> None:
    """Validate that ``state`` is structurally interchangeable with
    ``like`` (same pytree structure, leaf shapes and dtypes) - the guard a
    replan runs before handing restored/globalized state to a newly
    compiled train step.  Raises ValueError naming the first offending
    leaf path."""
    paths_a = {jax.tree_util.keystr(p): l for p, l in jax.tree_util.tree_leaves_with_path(state)}
    paths_b = {jax.tree_util.keystr(p): l for p, l in jax.tree_util.tree_leaves_with_path(like)}
    for path in sorted(set(paths_a) | set(paths_b)):
        if path not in paths_a:
            raise ValueError(f"state missing leaf {path!r} expected by plan")
        if path not in paths_b:
            raise ValueError(f"state has extra leaf {path!r} not in plan")
        a, b = paths_a[path], paths_b[path]
        if tuple(a.shape) != tuple(b.shape):
            raise ValueError(
                f"state leaf {path!r} shape {tuple(a.shape)} != plan {tuple(b.shape)}"
            )
        if jnp.dtype(a.dtype) != jnp.dtype(b.dtype):
            raise ValueError(
                f"state leaf {path!r} dtype {a.dtype} != plan {b.dtype}"
            )
