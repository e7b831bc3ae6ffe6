"""YOLOv2 first-16-layers (the paper's evaluation network, §5).

Darknet-19 prefix: conv3x3(+BN+leaky) / maxpool stages, 416x416 -> 26x26x512
feature maps.  The paper trains exactly these feature-map-dominated layers
distributed over tiles; we reproduce that with ``core.fusion`` grouped
stacks.

Resolution note (DESIGN.md §2): the Pi experiments use 416x416 with ragged
tiles per process.  TPU SPMD needs uniform shards, so mesh-wide runs use
512x512 - a resolution inside YOLOv2's own multi-scale training set - which
divides evenly on every layer for tile grids up to 16x16.  The 416 geometry
is still exercised by the cost model and the 2x2-grid exactness tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.fusion import (
    StackPlan,
    build_stack_plan,
    make_deferred_grad_step,
    make_tiled_forward,
    make_tiled_loss,
)
from repro.core.spatial import LayerDef, init_stack_params
from repro.core.tiling import Group, no_grouping
from repro.models.tiled_cnn import TiledCNNArch


def yolov2_16_layers(in_ch: int = 3, batch_norm: bool = True) -> list[LayerDef]:
    c = lambda cin, cout, k: LayerDef(
        k, 1, cin, cout, act="leaky", batch_norm=batch_norm, use_bias=not batch_norm
    )
    p = lambda ch: LayerDef(2, 2, ch, ch, pool=True, act="linear")
    return [
        c(in_ch, 32, 3),     # 1
        p(32),               # 2
        c(32, 64, 3),        # 3
        p(64),               # 4
        c(64, 128, 3),       # 5
        c(128, 64, 1),       # 6
        c(64, 128, 3),       # 7
        p(128),              # 8
        c(128, 256, 3),      # 9
        c(256, 128, 1),      # 10
        c(128, 256, 3),      # 11
        p(256),              # 12
        c(256, 512, 3),      # 13
        c(512, 256, 1),      # 14
        c(256, 512, 3),      # 15
        c(512, 256, 1),      # 16
    ]


def make_plan(
    input_hw: tuple[int, int] = (512, 512),
    n: int = 2,
    m: int = 2,
    groups=None,
    batch_norm: bool = True,
) -> StackPlan:
    layers = yolov2_16_layers(batch_norm=batch_norm)
    return build_stack_plan(input_hw, layers, n, m, groups)


def init_yolo(key, plan: StackPlan, dtype=jnp.float32):
    return init_stack_params(key, plan.layers, dtype)


def l2_loss_local(y: jax.Array, t: jax.Array):
    """Per-tile (sum, count) - the paper measures the training cycle, so a
    dense regression target over the output feature map stands in for the
    detection head (which lives beyond layer 16)."""
    d = (y - t).astype(jnp.float32)
    return jnp.sum(d * d), jnp.float32(d.size)


def make_yolo_tiled_arch(
    input_hw: tuple[int, int] = (64, 64),
    depth: int = 8,
    n: int = 2,
    m: int = 2,
    groups=None,
    *,
    backend: str = "xla",
    schedule: str = "sync",
    hw=None,
    batch: int = 1,
    crossover: int | str | None = None,
    mem_limit: float | None = None,
    partition=None,
    pipeline: int | str | None = None,
    microbatches: int | None = None,
    wire_codec: str = "none",
    batch_norm: bool = True,
    mesh=None,
    loss_local=l2_loss_local,
) -> TiledCNNArch:
    """Planner -> arch bundle for the unified trainer: a YOLOv2 prefix of
    ``depth`` layers tiled n x m, with the conv backend, executor schedule
    ("sync" | "overlap"), grouping profile (including ``groups="auto"``
    cost-model selection) and spatial->data ``crossover`` (None | layer
    index | "auto"; DESIGN.md §7) chosen at plan time.  ``hw`` may be a
    ``HardwareProfile``, a ``ClusterSpec`` (or cluster spec string like
    ``"pi3x3+jetson"``) for heterogeneous grids, and ``partition`` an
    explicit ``TilePartition`` (DESIGN.md §8).  ``pipeline``
    (None | "auto" | stage count; DESIGN.md §11) asks the planner for a
    pipeline tail over device subsets - requires ``groups="auto"`` and
    ``batch_norm=False`` layers in the tail; ``microbatches`` feeds the
    bubble model (defaults to the planner's standard M).  ``wire_codec``
    (``"none" | "int8" | "topk:<k>"``; DESIGN.md §12) compresses the
    per-sample collectives and biases the planner's comm terms to match."""
    return plan_tiled_arch(
        yolov2_16_layers(batch_norm=batch_norm)[:depth], input_hw, n, m, groups,
        backend=backend, schedule=schedule, hw=hw, batch=batch,
        crossover=crossover, mem_limit=mem_limit, partition=partition,
        pipeline=pipeline, microbatches=microbatches, wire_codec=wire_codec,
        mesh=mesh, loss_local=loss_local,
    )


def plan_tiled_arch(
    layers,
    input_hw: tuple[int, int],
    n: int,
    m: int,
    groups=None,
    *,
    microbatches: int | None = None,
    mesh=None,
    loss_local=l2_loss_local,
    **plan_kw,
) -> TiledCNNArch:
    """Plan ``layers`` tiled n x m (``build_stack_plan``'s keywords in
    ``plan_kw``) and bundle the plan with its mesh and loss for the trainer."""
    from repro.core.grouping import PIPELINE_MICROBATCHES
    from repro.launch.mesh import make_tile_mesh

    plan = build_stack_plan(
        input_hw, layers, n, m, groups,
        microbatches=PIPELINE_MICROBATCHES if microbatches is None else microbatches,
        **plan_kw,
    )
    return TiledCNNArch(
        plan=plan,
        mesh=mesh if mesh is not None else make_tile_mesh(n, m),
        loss_local=loss_local,
    )


def make_yolo_train_fns(
    plan: StackPlan,
    mesh,
    microbatches: int = 1,
    row_axis: str = "th",
    col_axis: str = "tw",
):
    """Returns (forward, loss, deferred_grad_step) shard_map'd over mesh.

    On the production mesh the tile grid rides the ("data", "model") axes -
    tile-row exchanges cross the data axis, tile-col exchanges the model
    axis."""
    ax = dict(row_axis=row_axis, col_axis=col_axis)
    fwd = make_tiled_forward(plan, mesh, **ax)
    loss = make_tiled_loss(plan, mesh, l2_loss_local, **ax)
    step = make_deferred_grad_step(
        plan, mesh, l2_loss_local, microbatches=microbatches, **ax
    )
    return fwd, loss, step
