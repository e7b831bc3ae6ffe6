"""Fused, grouped execution stacks (paper §4.2, Figs. 1/3/4).

A *stack* is the sequence of conv/pool layers fused onto one tile: the tile's
core never leaves its device; only group-input halos move.  A *grouping
profile* chooses where halo exchanges happen: inside a group each tile
carries a recursively-grown halo and recomputes boundary regions redundantly
(paper eq. 1 growth), trading compute for synchronisation.

``StackPlan`` precomputes all static geometry (group halo widths, per-layer
remaining halos, shard extents) so the shard_map'd executor contains no
Python-level geometry at trace time beyond table lookups.

Halo-width algebra (derived from eq. 1 recursion, DESIGN.md §2):

    group_halo_lo = sum_l P_l * prod_{l'<l in group} S_l'
    group_halo_hi = sum_l (K_l - S_l - P_l) * prod_{l'<l in group} S_l'

and the remaining halo after layer l shrinks as (h - P_l) / S_l (always
integral by construction).
"""
from __future__ import annotations

import dataclasses
import functools
import logging
import re
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro import obs
from repro.core.tiling import (
    Group,
    TilePartition,
    apply_crossover,
    bounds_sizes,
    crossover_of,
    dedup_axis_shapes,
    derive_axis_bounds,
    no_grouping,
    pipeline_first_of,
    validate_profile,
)
from repro.core.halo import (
    EFBag,
    WireCtx,
    halo_exchange_2d,
    halo_exchange_2d_ragged,
    halo_exchange_2d_spec,
    static_table_lookup,
    wire_shift,
)
from repro.optim.compression import get_codec
from repro.core.backend import get_conv_backend
from repro.core.spatial import (
    LayerDef,
    apply_group_lead_overlap,
    apply_layer_data,
    apply_layer_local,
    apply_layer_local_ragged,
    apply_layer_local_spec,
    check_shortcuts,
    reshard_spatial_to_data,
    reshard_spatial_to_data_ragged,
    shortcut_sources,
    SkipStore,
    stack_reference,
)
from repro.core.grouping import (
    ClusterSpec,
    HardwareProfile,
    PI3_PROFILE,
    PIPELINE_MICROBATCHES,
    PROFILES,
    check_crossover_arg,
    check_pipeline_arg,
    cluster_partition,
    feasible_stage_counts,
    optimize_grouping,
    parse_cluster_spec,
    profile_cost,
    score_profile,
    shortcut_group_error,
    shortcut_pipeline_error,
    shortcut_tail_error,
)


@dataclasses.dataclass(frozen=True)
class StackPlan:
    """Static geometry for an (n x m)-tiled, grouped conv stack.

    Each group carries a partition ``mode`` ("spatial" | "data" |
    "pipeline"); when a data suffix exists, ``crossover`` records its first
    layer - the point where the executor reshards the tile grid into batch
    shards (DESIGN.md §7).  ``shard_hw`` entries at data-mode layer inputs
    are the *full* map extents (nothing is spatially sharded there).

    A pipeline tail (DESIGN.md §11) assigns each pipeline-mode group - a
    *stage* - to its own contiguous flat-device subset: ``stages[s] =
    (lo, hi)`` is the half-open flat-index range (``r = i*m + j``) stage
    ``s`` owns.  Stage subsets are equal-sized and row-aligned so the
    inter-stage activation hand-off is ONE axis-aligned ``ppermute``;
    microbatches stream through the stages on a fill/drain tick schedule
    and, like data layers, pipeline layers hold full map extents.

    The tile grid is an explicit ``TilePartition`` (DESIGN.md §8):
    ``tile_rows[l]`` / ``tile_cols[l]`` are the per-tile owned extents at
    each layer input (full-extent entries past the crossover), and
    ``shard_hw`` is the *padded* (max-tile) shard extent.  Uniform
    partitions (every tile equal) run the legacy executor byte-for-byte;
    non-uniform ones run the shape-specialized executor (``ragged_exec=
    "spec"``, DESIGN.md §9) or the padded-to-max fallback (``"padded"``,
    DESIGN.md §8).
    """

    layers: tuple[LayerDef, ...]
    groups: tuple[Group, ...]
    n: int
    m: int
    input_hw: tuple[int, int]
    map_hw: tuple[tuple[int, int], ...]          # extent at each layer input; [-1] = output
    shard_hw: tuple[tuple[int, int], ...]        # (padded) shard extent per layer input
    group_halos: tuple[tuple[int, int, int, int], ...]   # (top,bot,left,right) @ group input
    rem_halos: tuple[tuple[int, int, int, int], ...]     # remaining halo after each layer
    group_of_layer: tuple[int, ...]
    backend: str = "xla"                         # conv compute path (core.backend)
    schedule: str = "sync"                       # "sync" | "overlap" (DESIGN.md §5)
    block_oh: int | None = None                  # conv output-row block (None = auto)
    crossover: int | None = None                 # first data-mode layer (None = all spatial)
    partition: TilePartition | None = None       # input-level tile boundaries
    tile_rows: tuple[tuple[int, ...], ...] = ()  # per layer input: per-tile-row extents
    tile_cols: tuple[tuple[int, ...], ...] = ()
    ragged_exec: str = "spec"                    # non-uniform executor (DESIGN.md §9)
    stages: tuple[tuple[int, int], ...] = ()     # per pipeline stage: flat device range
    wire_codec: str = "none"                     # per-sample collective codec (DESIGN.md §12)
    inference: bool = False                      # forward-only serve plan (DESIGN.md §13)

    @property
    def n_layers(self) -> int:
        return len(self.layers)

    def inference_twin(self) -> "StackPlan":
        """The forward-only serving twin of this plan (DESIGN.md §13): same
        geometry, partition, and compute-path knobs, but BN runs from frozen
        statistics (no cross-device psum) and the executor is used strictly
        as a pure SPMD forward.  Pipeline plans have no serve twin - their
        outputs live on the last stage only."""
        if self.stages:
            raise ValueError(
                "pipeline plans have no inference twin: serve steps need a "
                "single-shot forward layout; replan without the pipeline tail"
            )
        return dataclasses.replace(self, inference=True)

    def out_hw(self) -> tuple[int, int]:
        return self.map_hw[-1]

    @property
    def pipeline_first(self) -> int | None:
        """First pipeline-mode layer index (None = no pipeline tail)."""
        return pipeline_first_of(self.groups)

    @property
    def n_stages(self) -> int:
        return len(self.stages)

    @property
    def spatial_last(self) -> int:
        """Deepest spatially-sharded layer-input index (first non-spatial
        layer, or the stack output for all-spatial plans)."""
        if self.crossover is not None:
            return self.crossover
        pf = self.pipeline_first
        return self.n_layers if pf is None else pf

    @property
    def is_uniform(self) -> bool:
        """True when every tile has the same shape at every spatially-
        sharded layer - the equal-boundary special case that runs the
        legacy (padding-free) executor and reproduces pre-partition jaxprs
        exactly."""
        if not self.tile_rows:
            return True     # directly-constructed legacy plans
        return all(
            len(set(self.tile_rows[l])) == 1 and len(set(self.tile_cols[l])) == 1
            for l in range(self.spatial_last + 1)
        )


def _check_shortcut_plan(layers, groups, map_hw, schedule, cross, pfirst) -> None:
    """Plan-time refusals for stacks with shortcuts (DESIGN.md §16): the
    uniform sync executor and the data tail run them; the overlap schedule
    and pipeline tails do not, and a group edge or crossover may sit inside
    a residual unit only where the skip needs no halo."""
    if not shortcut_sources(layers):
        return
    check_shortcuts(layers, map_hw)
    first = min(shortcut_sources(layers))
    if schedule == "overlap":
        raise ValueError(
            f"shortcut layer {first} (from={layers[first].shortcut}): the "
            "overlap schedule does not run shortcuts; use schedule='sync'"
        )
    if pfirst is not None:
        raise ValueError(shortcut_pipeline_error(layers))
    err = shortcut_tail_error(layers, cross)
    for g in groups:
        if err is None and g.mode == "spatial":
            err = shortcut_group_error(layers, g.start, g.end)
    if err:
        raise ValueError(err)


def resolve_hw_profile(hw: HardwareProfile | ClusterSpec | str | None):
    """Profile object from a profile, a ClusterSpec, a registered name, or
    None (Pi default)."""
    if hw is None:
        return PI3_PROFILE
    if isinstance(hw, str):
        try:
            return PROFILES[hw]
        except KeyError:
            raise KeyError(
                f"unknown hardware profile {hw!r}; available: {sorted(PROFILES)}"
            ) from None
    return hw


def _resolve_hw(hw, n: int, m: int):
    """Like ``resolve_hw_profile`` but also accepts cluster spec strings
    ("pi3x3+jetson") - resolvable only here, where the grid is known.
    Strings that *look* like cluster specs ('+'-joined or counted parts)
    surface parse_cluster_spec's own error (device-count mismatch, unknown
    device) instead of the misleading unknown-profile KeyError."""
    if isinstance(hw, str) and hw not in PROFILES:
        if "+" in hw or re.search(r"x\d+$", hw):
            return parse_cluster_spec(hw, n, m)
    return resolve_hw_profile(hw)


def _resolve_crossover(
    input_hw,
    layers,
    groups: tuple[Group, ...],
    crossover: int | str | None,
    n: int,
    m: int,
    hw,
    batch: int,
    schedule: str,
    mem_limit: float | None = None,
    partition: TilePartition | None = None,
    wire_codec: str = "none",
) -> tuple[Group, ...]:
    """Assign partition modes to an *explicit* grouping profile.

    ``crossover=None`` keeps the modes the groups already carry; an int
    forces the spatial->data transition at that layer (must align with a
    group boundary; L = all-spatial, same as the optimizer's convention);
    ``"auto"`` scores every group boundary (and "none") through the same
    ``grouping.score_profile`` routine the joint optimizer uses (cost +
    mem_limit feasibility) and keeps the cheapest."""
    if crossover is None:
        return groups
    check_crossover_arg(crossover, len(layers))
    if isinstance(crossover, int):
        return tuple(apply_crossover(groups, crossover))
    best = None
    for c in [None] + [g.start for g in groups]:
        if shortcut_tail_error(layers, c):
            continue
        cand = tuple(apply_crossover(groups, c))
        cost = score_profile(
            input_hw, layers, cand, n, m, hw, batch, schedule, mem_limit,
            partition=partition, wire_codec=wire_codec,
        )
        if cost is None:
            continue
        if best is None or cost < best[0]:
            best = (cost, cand)
    if best is None:
        raise ValueError(
            f"no crossover candidate of this profile fits mem_limit={mem_limit}"
        )
    return best[1]


def _resolve_auto_schedule(
    input_hw, layers, groups, n, m, hw, batch, partition
) -> str:
    """Resolve ``schedule="auto"`` to a concrete schedule (DESIGN.md §5).

    Overlap pays only when (a) the backend can actually run collectives
    concurrently with compute (gpu/tpu async collectives + latency-hiding
    scheduler; the host CPU backend runs them inline, which is why overlap
    *measures* >1.0 overhead there despite modeling faster) and (b) the
    cost model predicts a non-trivial hidden term.  Heterogeneous clusters
    stay on sync: the overlap interior/boundary split applies only to
    uniform groups, and ragged groups run the sync exchange anyway."""
    from repro import compat

    if (isinstance(hw, ClusterSpec) or not compat.overlap_supported()
            or shortcut_sources(layers)):
        return "sync"
    cand = (
        tuple(groups)
        if groups is not None and not isinstance(groups, str)
        else tuple(no_grouping(len(layers)))
    )
    cost = profile_cost(
        input_hw, tuple(layers), cand, n, m, resolve_hw_profile(hw),
        batch, "overlap", partition=partition,
    )
    return "overlap" if cost["hidden"] > 0.01 * cost["total"] else "sync"


def build_stack_plan(
    input_hw: tuple[int, int],
    layers: Sequence[LayerDef],
    n: int,
    m: int,
    groups: Sequence[Group] | str | None = None,
    *,
    backend: str = "xla",
    schedule: str = "sync",
    block_oh: int | None = None,
    hw: HardwareProfile | ClusterSpec | str | None = None,
    batch: int = 1,
    crossover: int | str | None = None,
    mem_limit: float | None = None,
    partition: TilePartition | None = None,
    ragged_exec: str = "spec",
    pipeline: int | str | None = None,
    microbatches: int = PIPELINE_MICROBATCHES,
    wire_codec: str = "none",
    inference: bool = False,
) -> StackPlan:
    """Planner: all static geometry + compute-path choices for a tiled stack.

    inference (DESIGN.md §13): plan a *forward-only* serve step - BN runs
    from frozen ``bn_mean``/``bn_var`` statistics instead of cross-device
    batch psums, so the executor emits no training-only collective and a
    serve step is one pure SPMD forward.  Incompatible with pipeline tails
    (no single-shot output layout); every other knob (backend, schedule,
    crossover, partition, ragged_exec, wire_codec) composes unchanged.

    groups: explicit profile, None (= sync every layer), or ``"auto"`` - run
    the DP cost-model optimizer (core.grouping) against ``hw`` (a
    HardwareProfile, a registered profile name, or None for the Pi default)
    at batch size ``batch``, so grouping selection flows into the plan
    instead of living in a side tool.  backend: registered conv compute path
    ("xla" | "pallas"); validated here so a typo fails at plan time, not
    inside shard_map tracing.  schedule: "sync" (eager halo exchange, the
    exactness oracle), "overlap" (packed collectives + interior/boundary
    split execution, DESIGN.md §5), or "auto" (overlap only when the
    backend can hide collectives AND the modelled hidden term is
    non-trivial - ``_resolve_auto_schedule``); flows into the cost model
    when ``groups="auto"`` so grouping selection reflects communication
    hiding.
    block_oh: the conv backend's output-row VMEM block (None = auto from the
    kernel's accumulator budget); planner-controlled so the executor's VMEM
    footprint is a plan-time choice, threaded to every backend call.

    crossover (DESIGN.md §7): where the plan switches from spatial tiling
    to data parallelism.  ``None`` respects whatever modes the groups carry
    (all-spatial for plain profiles - full backward compatibility); an int
    pins the first data-mode layer (must align with a group boundary);
    ``"auto"`` lets the cost model choose - jointly with the grouping when
    ``groups="auto"`` (the DP scans every candidate crossover), else among
    the given profile's boundaries.  ``mem_limit`` (bytes/device) bounds
    the modelled peak working set during ``groups="auto"`` selection.

    partition (DESIGN.md §8): explicit input-level ``TilePartition``
    boundary arrays.  ``None`` derives a default: the FLOPs-balanced
    makespan partition when ``hw`` is a ``ClusterSpec`` (or a cluster spec
    string like ``"pi3x3+jetson"``), else the stride-aligned ragged-even
    split - which *is* the old uniform grid whenever the extents divide, so
    existing plans are bit-identical, and which replaces the old
    divisibility ``ValueError`` for ragged extents (a 7x7 map on a 2x2 mesh
    now plans as 4+3 tile rows).  Non-uniform partitions run the
    shape-specialized executor (``ragged_exec="spec"``, DESIGN.md §9:
    per-shape programs selected by ``lax.switch`` on the axis index - no
    dynamic slicing, no wasted MACs on pad slots) or the padded-to-max
    fallback (``ragged_exec="padded"``, DESIGN.md §8); the overlap
    schedule's interior/boundary split applies only to uniform groups
    (ragged groups use the sync exchange).

    pipeline (DESIGN.md §11): ``None`` keeps pipeline tails out of the
    search; ``"auto"`` lets the grouping DP add pipeline-tail candidates
    (entry layer x stage count, bubble + transfer cost terms) to the same
    comparison; an int forces that many stages.  Planner-assigned only -
    requires ``groups="auto"`` (explicit profiles may carry pipeline-mode
    groups directly, e.g. from a plan manifest).  ``microbatches`` is the
    per-batch microbatch count the bubble fraction (S-1)/(S-1+M) is
    modelled against; the executor's actual M is set at
    ``make_deferred_grad_step(microbatches=...)`` time.
    """
    get_conv_backend(backend)   # fail fast on unknown backends
    if schedule not in ("sync", "overlap", "auto"):
        raise ValueError(
            f"schedule must be 'sync', 'overlap', or 'auto'; got {schedule!r}"
        )
    if ragged_exec not in ("spec", "padded"):
        raise ValueError(
            f"ragged_exec must be 'spec' or 'padded'; got {ragged_exec!r}"
        )
    if block_oh is not None and block_oh < 1:
        raise ValueError(f"block_oh must be a positive int or None; got {block_oh!r}")
    get_codec(wire_codec)   # fail fast on bad codec specs (none | int8 | topk:<k>)
    layers = tuple(layers)
    if inference and pipeline is not None:
        raise ValueError(
            "inference plans cannot carry a pipeline tail: a serve step "
            "needs a single-shot forward layout, but pipeline outputs live "
            "on the last stage's devices only; plan with pipeline=None"
        )
    check_pipeline_arg(pipeline, n, m, len(layers))
    if pipeline is not None:
        if schedule == "overlap":
            raise ValueError(
                "schedule='overlap' cannot combine with a pipeline tail: the "
                "interior/boundary split assumes every device runs the same "
                "halo exchange, but pipeline stages run disjoint layer "
                "programs; use schedule='sync' (or 'auto', which resolves "
                "to sync for pipeline plans)"
            )
        if groups is None or not isinstance(groups, str):
            raise ValueError(
                "pipeline tails are planner-assigned: use groups='auto' "
                "with pipeline=..., or pass an explicit profile that "
                "already carries pipeline-mode groups (e.g. from a plan "
                "manifest) without the pipeline kwarg"
            )
        schedule = "sync" if schedule == "auto" else schedule
    hw = _resolve_hw(hw, n, m) if hw is not None else None
    if schedule == "auto":
        schedule = _resolve_auto_schedule(
            input_hw, layers, groups, n, m, hw, batch, partition
        )
    if isinstance(hw, ClusterSpec) and (hw.n, hw.m) != (n, m):
        raise ValueError(f"cluster grid {(hw.n, hw.m)} != tile grid {(n, m)}")
    if partition is not None and (partition.n, partition.m) != (n, m):
        raise ValueError(
            f"partition grid {(partition.n, partition.m)} != tile grid {(n, m)}"
        )
    if isinstance(groups, str):
        if groups != "auto":
            raise ValueError(f"groups must be a profile, None, or 'auto'; got {groups!r}")
        groups = tuple(
            optimize_grouping(
                input_hw, layers, n, m,
                hw if isinstance(hw, ClusterSpec) else resolve_hw_profile(hw),
                batch=batch, schedule=schedule, crossover=crossover,
                mem_limit=mem_limit, partition=partition,
                pipeline=pipeline, microbatches=microbatches,
                wire_codec=wire_codec,
            )
        )
    else:
        if groups is None:
            groups = tuple(no_grouping(len(layers)))
        else:
            groups = tuple(groups)
        groups = _resolve_crossover(
            input_hw, layers, groups, crossover, n, m,
            hw if isinstance(hw, ClusterSpec) else resolve_hw_profile(hw),
            batch, schedule, mem_limit, partition, wire_codec,
        )
    validate_profile(groups, len(layers))
    cross = crossover_of(groups)
    pfirst = pipeline_first_of(groups)
    if inference and pfirst is not None:
        raise ValueError(
            "inference plans cannot carry pipeline-mode groups: a serve "
            "step needs a single-shot forward layout; use a spatial/data "
            "grouping profile"
        )

    # Pipeline tails: derive the per-stage device subsets (equal contiguous
    # flat ranges) and check the executor's structural requirements early,
    # with actionable errors instead of deep shard_map failures.
    stages: tuple[tuple[int, int], ...] = ()
    if pfirst is not None:
        pipe_groups = [g for g in groups if g.mode == "pipeline"]
        s_count = len(pipe_groups)
        tail_layers = len(layers) - pfirst
        if s_count not in feasible_stage_counts(n, m, tail_layers):
            raise ValueError(
                f"{s_count} pipeline stages are infeasible on the {n}x{m} "
                f"grid with a {tail_layers}-layer tail: stage subsets must "
                "be equal-sized and row-aligned (n==1, m==1, or "
                "devices-per-stage divisible by m) so the inter-stage "
                "hand-off is one axis-aligned ppermute; feasible counts: "
                f"{feasible_stage_counts(n, m, tail_layers) or 'none'}"
            )
        for g in pipe_groups:
            for l in g.layers:
                if layers[l].batch_norm:
                    raise ValueError(
                        f"layer {l} has batch_norm=True inside a pipeline "
                        "stage: BN needs cross-device psums, which cannot "
                        "live inside the per-stage lax.switch branches; "
                        "keep BN layers in the spatial prefix or build the "
                        "stack with batch_norm=False"
                    )
        if schedule == "overlap":
            raise ValueError(
                "schedule='overlap' cannot combine with a pipeline tail; "
                "use schedule='sync'"
            )
        per_stage = (n * m) // s_count
        stages = tuple((s * per_stage, (s + 1) * per_stage) for s in range(s_count))

    # Map extents per layer input ([-1] = output).
    map_hw = [tuple(input_hw)]
    for l in layers:
        h, w = map_hw[-1]
        map_hw.append((l.out_extent(h), l.out_extent(w)))
    _check_shortcut_plan(layers, groups, map_hw, schedule, cross, pfirst)

    # Resolve the tile partition over the spatial prefix (through the
    # first non-spatial layer's input; data- and pipeline-mode layers hold
    # full maps and are exempt).
    tail_first = cross if cross is not None else pfirst
    last = len(layers) if tail_first is None else tail_first
    strides = [l.stride for l in layers[:last]]
    hs = [map_hw[l][0] for l in range(last + 1)]
    ws = [map_hw[l][1] for l in range(last + 1)]
    if partition is None and isinstance(hw, ClusterSpec):
        partition = cluster_partition(input_hw, layers, hw, tail_first)
    try:
        row_bounds = derive_axis_bounds(
            partition.row_bounds if partition else None, strides, hs, n
        )
        col_bounds = derive_axis_bounds(
            partition.col_bounds if partition else None, strides, ws, m
        )
    except ValueError as e:
        raise ValueError(
            f"cannot partition map extents over the {n}x{m} tile grid: {e}; "
            "use a coarser grid, an earlier crossover, or different boundaries"
        ) from None
    if partition is None:
        partition = TilePartition(row_bounds[0], col_bounds[0])

    tile_rows = [bounds_sizes(b) for b in row_bounds]
    tile_cols = [bounds_sizes(b) for b in col_bounds]
    shard_hw = [(max(r), max(c)) for r, c in zip(tile_rows, tile_cols)]
    for li in range(last + 1, len(layers) + 1):
        h, w = map_hw[li]
        tile_rows.append((h,) * n)
        tile_cols.append((w,) * m)
        shard_hw.append((h, w))

    if pfirst is not None and any(
        len(set(tile_rows[l])) > 1 or len(set(tile_cols[l])) > 1
        for l in range(last + 1)
    ):
        raise ValueError(
            "pipeline plans require a uniform tile partition over the "
            "spatial prefix (the stage-entry gather slices equal "
            "microbatch blocks); rebalance the partition or drop the "
            "pipeline tail"
        )

    # Group halos + per-layer remaining halos (zero for data- and
    # pipeline-mode groups: full maps have no neighbours).
    group_halos: list[tuple[int, int, int, int]] = []
    rem_halos: list[tuple[int, int, int, int]] = [None] * len(layers)  # type: ignore
    group_of_layer: list[int] = [0] * len(layers)
    for gi, g in enumerate(groups):
        if g.mode != "spatial":
            group_halos.append((0, 0, 0, 0))
            for l in g.layers:
                group_of_layer[l] = gi
                rem_halos[l] = (0, 0, 0, 0)
            continue
        hl = hh = 0
        sprod = 1
        for l in g.layers:
            p = layers[l].padding
            q = layers[l].kernel - layers[l].stride - p
            hl += p * sprod
            hh += q * sprod
            sprod *= layers[l].stride
        group_halos.append((hl, hh, hl, hh))
        # The exchange ships at most one neighbour strip per side, so the
        # group halo must fit inside the smallest neighbouring tile.
        if min(tile_rows[g.start]) < max(hl, hh) or min(tile_cols[g.start]) < max(hl, hh):
            raise ValueError(
                f"group ({g.start}, {g.end}) halo ({hl}, {hh}) exceeds the "
                f"smallest tile of partition rows={tile_rows[g.start]} "
                f"cols={tile_cols[g.start]}; use a finer grouping or a less "
                "skewed partition"
            )
        # remaining halo after each layer inside the group
        cur_lo, cur_hi = hl, hh
        for l in g.layers:
            group_of_layer[l] = gi
            p = layers[l].padding
            q = layers[l].kernel - layers[l].stride - p
            cur_lo = (cur_lo - p) // layers[l].stride
            cur_hi = (cur_hi - q) // layers[l].stride
            rem_halos[l] = (cur_lo, cur_hi, cur_lo, cur_hi)
        assert cur_lo == 0 and cur_hi == 0, "halo must be consumed by group end"

    plan = StackPlan(
        layers=layers,
        groups=groups,
        n=n,
        m=m,
        input_hw=tuple(input_hw),
        map_hw=tuple(map_hw),
        shard_hw=tuple(shard_hw),
        group_halos=tuple(group_halos),
        rem_halos=tuple(rem_halos),
        group_of_layer=tuple(group_of_layer),
        backend=backend,
        schedule=schedule,
        block_oh=block_oh,
        crossover=cross,
        partition=partition,
        tile_rows=tuple(tile_rows),
        tile_cols=tuple(tile_cols),
        ragged_exec=ragged_exec,
        stages=stages,
        wire_codec=wire_codec,
        inference=inference,
    )
    if shortcut_sources(layers) and not plan.is_uniform:
        l = min(shortcut_sources(layers))
        raise ValueError(
            f"shortcut layer {l} (from={layers[l].shortcut}): the ragged "
            f"executors do not run shortcuts, and partition rows={tile_rows[0]} "
            f"cols={tile_cols[0]} is not uniform; use an even partition"
        )
    return plan


# ---------------------------------------------------------------------------
# Elastic plans: manifest serialization + replanning onto a changed cluster
# (DESIGN.md §10)
# ---------------------------------------------------------------------------

_log = logging.getLogger("repro.core")

# v2 added "wire_codec" (DESIGN.md §12); v1 manifests read back as "none".
# v3 added "inference" (DESIGN.md §13); v1/v2 manifests read back as False.
PLAN_MANIFEST_VERSION = 3


def plan_manifest(plan: StackPlan, cluster: ClusterSpec | None = None) -> dict:
    """JSON-serializable description of a StackPlan for the checkpoint
    manifest: layer stack, tile grid, partition boundaries, grouping
    profile (with per-group modes/crossover), backend/schedule knobs, and
    optionally the ClusterSpec the plan was balanced for.

    This is *metadata*: checkpoints store global (untiled) params and
    optimizer state, so restore never needs the manifest to reconstruct
    arrays - it exists so an operator (or ``--resume``) can see what
    partition a run was using, and so ``plan_from_manifest`` can rebuild
    the exact plan when the same cluster is still present."""
    from repro.core.grouping import cluster_manifest

    return {
        "version": PLAN_MANIFEST_VERSION,
        "input_hw": list(plan.input_hw),
        "n": plan.n,
        "m": plan.m,
        "layers": [dataclasses.asdict(l) for l in plan.layers],
        "groups": [[g.start, g.end, g.mode] for g in plan.groups],
        "crossover": plan.crossover,
        # informational: stage device ranges are re-derived from the groups
        # by build_stack_plan, so plan_from_manifest never reads this key
        "stages": [list(s) for s in plan.stages],
        "partition": None
        if plan.partition is None
        else {
            "row_bounds": list(plan.partition.row_bounds),
            "col_bounds": list(plan.partition.col_bounds),
        },
        "backend": plan.backend,
        "schedule": plan.schedule,
        "block_oh": plan.block_oh,
        "ragged_exec": plan.ragged_exec,
        "wire_codec": plan.wire_codec,
        "inference": plan.inference,
        "cluster": None if cluster is None else cluster_manifest(cluster),
    }


def plan_from_manifest(man: dict) -> StackPlan:
    """Rebuild the StackPlan a manifest describes - explicit groups and
    partition, so the planner re-derives all geometry deterministically and
    the result is dataclass-equal to the plan that was saved."""
    layers = tuple(LayerDef(**ld) for ld in man["layers"])
    groups = tuple(Group(s, e, mode) for s, e, mode in man["groups"])
    part = man.get("partition")
    partition = (
        None
        if part is None
        else TilePartition(tuple(part["row_bounds"]), tuple(part["col_bounds"]))
    )
    return build_stack_plan(
        tuple(man["input_hw"]),
        layers,
        man["n"],
        man["m"],
        groups,
        backend=man.get("backend", "xla"),
        schedule=man.get("schedule", "sync"),
        block_oh=man.get("block_oh"),
        partition=partition,
        ragged_exec=man.get("ragged_exec", "spec"),
        wire_codec=man.get("wire_codec", "none"),
        inference=man.get("inference", False),
    )


def replan_stack(
    plan: StackPlan,
    hw: HardwareProfile | ClusterSpec | str | None,
    n: int | None = None,
    m: int | None = None,
    *,
    batch: int = 1,
    groups: Sequence[Group] | str | None = "auto",
    crossover: int | str | None = "auto",
    mem_limit: float | None = None,
    partition: TilePartition | None = None,
    pipeline: int | str | None = None,
) -> StackPlan:
    """Rebuild ``plan`` against a changed cluster (elastic replan,
    DESIGN.md §10): same layer stack, same backend/schedule/executor knobs,
    new device set.  Re-runs the full planning pipeline - makespan
    balancing (``balance_bounds`` via ``cluster_partition``), the grouping
    DP (``groups="auto"``) and the crossover scan (``crossover="auto"``) -
    so the surviving devices get a partition balanced for *them*, not the
    one the lost device was part of.

    ``n``/``m`` default to the ClusterSpec's grid (required for other hw
    forms when the grid changes).  Params are partition-independent (every
    device holds full filters), so a TrainState trains on the new plan
    as-is once re-placed - see ``train.trainer.globalize_state``.

    Graceful degradation: if the cost-optimal grouping/crossover is
    infeasible under the rebalanced partition (a skewed survivor mesh can
    shrink the smallest tile below a fused group's halo), fall back to
    ungrouped layers, then to ungrouped all-spatial - a valid plan always
    comes back for any cluster the partitioner can balance.

    Pipeline plans degrade the same way: when the old plan carried a
    pipeline tail (or ``pipeline`` is passed explicitly), the first rung
    replans with ``pipeline="auto"`` so surviving devices get stages
    re-packed for *them* (the stage-count feasibility set shrinks with the
    grid); if no stage count fits, the same optimizer call already
    competes spatial/data candidates, and the later rungs drop the
    pipeline search entirely."""
    if isinstance(hw, ClusterSpec):
        n = hw.n if n is None else n
        m = hw.m if m is None else m
    if n is None or m is None:
        raise ValueError("replan_stack needs n, m when hw is not a ClusterSpec")
    if pipeline is None and plan.stages:
        pipeline = "auto"

    def attempt(g, x, p):
        return build_stack_plan(
            plan.input_hw,
            plan.layers,
            n,
            m,
            g,
            backend=plan.backend,
            schedule=plan.schedule,
            block_oh=plan.block_oh,
            hw=hw,
            batch=batch,
            crossover=x,
            mem_limit=mem_limit,
            partition=partition,
            ragged_exec=plan.ragged_exec,
            pipeline=p if g == "auto" else None,
            wire_codec=plan.wire_codec,
            inference=plan.inference,
        )

    ladder = [(groups, crossover, pipeline)]
    if pipeline is not None:
        ladder.append((groups, crossover, None))
    if groups is not None:
        ladder.append((None, crossover, None))
    if crossover is not None:
        ladder.append((None, None, None))
    last_err: Exception | None = None
    for i, (g, x, p) in enumerate(ladder):
        try:
            return attempt(g, x, p)
        except ValueError as e:
            last_err = e
            if i + 1 < len(ladder):
                _log.warning(
                    "replan with groups=%r crossover=%r pipeline=%r "
                    "infeasible (%s); degrading to groups=%r crossover=%r "
                    "pipeline=%r",
                    g, x, p, e, *ladder[i + 1],
                )
    raise last_err


# ---------------------------------------------------------------------------
# Shard-local executor (runs inside shard_map)
# ---------------------------------------------------------------------------


def _ragged_group_geom(plan: StackPlan, gi: int) -> dict:
    """Static geometry of one spatial group under the ragged executor
    (DESIGN.md §8): per-layer canonical (padded) extended extents.

    For layer k of the group (input halos (lo, hi), output halos (lo',
    hi')), a tile's *valid* extended input occupies rows [0, lo + own_i +
    hi) of the padded layout and its valid outputs rows [0, lo' + own'_i +
    hi').  The canonical static input extent must cover both the largest
    valid window and the largest window any tile's valid outputs read -
    ``(max_valid_out - 1) * stride + kernel`` (the last tile's off-map
    reach can exceed its valid input rows; those reads hit zeros = the
    global SAME padding)."""
    g = plan.groups[gi]
    halos = [plan.group_halos[gi]] + [plan.rem_halos[l] for l in g.layers]
    ein = []        # canonical extended input extent (rows, cols) per layer
    for k, l in enumerate(g.layers):
        top, bottom, left, right = halos[k]
        ntop, nbot, nleft, nright = halos[k + 1]
        ker, s = plan.layers[l].kernel, plan.layers[l].stride
        rows = max(
            max(plan.tile_rows[l]) + top + bottom,
            max(
                (ntop + r + nbot - 1) * s + ker for r in plan.tile_rows[l + 1]
            ),
        )
        cols = max(
            max(plan.tile_cols[l]) + left + right,
            max(
                (nleft + c + nright - 1) * s + ker for c in plan.tile_cols[l + 1]
            ),
        )
        ein.append((rows, cols))
    # canonical output extent of layer k = input extent of layer k+1; the
    # group-end output is the padded core (next group re-exchanges halos)
    eout = ein[1:] + [(max(plan.tile_rows[g.end + 1]), max(plan.tile_cols[g.end + 1]))]
    return {"ein": ein, "eout": eout, "halos": halos}


def _offsets(sizes: tuple[int, ...]) -> tuple[int, ...]:
    out, acc = [], 0
    for s in sizes:
        out.append(acc)
        acc += s
    return tuple(out)


def _apply_group_ragged(
    x: jax.Array,
    params: Sequence[dict],
    plan: StackPlan,
    gi: int,
    *,
    row_axis: str,
    col_axis: str,
    batch_axis: str | None,
    batch_global: int,
    wire: WireCtx | None = None,
) -> jax.Array:
    """One spatial group on a ragged (non-uniform partition) tile.

    ``x`` enters as the padded core (b, Hmax, Wmax, c) with pad slots zero;
    the ragged halo exchange assembles the canonical extended tile with
    per-device dynamic strip offsets, then every layer runs conv ->
    refit-to-canonical-extent -> mask (``apply_layer_local_ragged``), which
    restores the padded-tile invariant for the next layer/group.  Runs the
    sync exchange regardless of ``plan.schedule`` - the overlap split's
    interior geometry is per-device and is left to future work."""
    g = plan.groups[gi]
    geom = _ragged_group_geom(plan, gi)
    i = jax.lax.axis_index(row_axis)
    j = jax.lax.axis_index(col_axis)
    with obs.layer_scope(g.start), jax.named_scope(obs.HALO):
        x = halo_exchange_2d_ragged(
            x,
            plan.group_halos[gi],
            row_axis,
            col_axis,
            plan.tile_rows[g.start],
            plan.tile_cols[g.start],
            dims=(1, 2),
            out_extents=geom["ein"][0],
            wire=wire,
        )
    for k, l in enumerate(g.layers):
        out_rows = plan.tile_rows[l + 1]
        out_cols = plan.tile_cols[l + 1]
        with obs.layer_scope(l):
            x = apply_layer_local_ragged(
                x,
                params[l],
                plan.layers[l],
                out_halo=geom["halos"][k + 1],
                out_size=(
                    jnp.asarray(out_rows, jnp.int32)[i],
                    jnp.asarray(out_cols, jnp.int32)[j],
                ),
                out_off=(
                    jnp.asarray(_offsets(out_rows), jnp.int32)[i],
                    jnp.asarray(_offsets(out_cols), jnp.int32)[j],
                ),
                canon_out_hw=geom["eout"][k],
                map_out_hw=plan.map_hw[l + 1],
                row_axis=row_axis,
                col_axis=col_axis,
                batch_global=batch_global,
                batch_axis=batch_axis,
                backend=plan.backend,
                block_oh=plan.block_oh,
                inference=plan.inference,
            )
    return x


def _apply_group_spec(
    x: jax.Array,
    params: Sequence[dict],
    plan: StackPlan,
    gi: int,
    *,
    row_axis: str,
    col_axis: str,
    batch_axis: str | None,
    batch_global: int,
    wire: WireCtx | None = None,
) -> jax.Array:
    """One spatial group on a shape-specialized ragged tile (DESIGN.md §9).

    The per-axis tile shapes are deduplicated at the group input
    (``dedup_axis_shapes``; stride alignment makes the group-start size the
    complete per-axis shape key, so a 2/62-row split compiles 2 row
    programs, not 4), and every layer runs an unrolled ``lax.switch`` over
    the <= len(runiq)*len(cuniq) distinct (row, col) shapes: each branch
    statically slices its valid extended window, convolves the TRUE extent,
    and sums BN statistics over the real core - no ``dynamic_slice``, no
    sizes tables, no wasted MACs on pad slots.  Collectives stay OUTSIDE
    the switches: the halo exchange ships static-width strips through two
    ``ppermute`` rounds (``halo_exchange_2d_spec``) and the BN psum runs on
    uniform per-branch avals.  Pad slots are garbage past each branch's
    valid window (no masking, except the off-map rim zeroing mid-group);
    safe because every consumer reads valid windows only."""
    g = plan.groups[gi]
    geom = _ragged_group_geom(plan, gi)
    i = lax.axis_index(row_axis)
    j = lax.axis_index(col_axis)
    with obs.layer_scope(g.start), jax.named_scope(obs.HALO):
        x = halo_exchange_2d_spec(
            x,
            plan.group_halos[gi],
            row_axis,
            col_axis,
            plan.tile_rows[g.start],
            plan.tile_cols[g.start],
            dims=(1, 2),
            out_extents=geom["ein"][0],
            wire=wire,
        )
    rtab, runiq = dedup_axis_shapes(plan.tile_rows[g.start])
    ctab, cuniq = dedup_axis_shapes(plan.tile_cols[g.start])
    branch = static_table_lookup(rtab, i) * len(cuniq) + static_table_lookup(ctab, j)
    # Cumulative stride products: group-start sizes divided by cum[k] give
    # the layer-k input tile sizes (stride alignment guarantees exactness).
    cum = [1]
    for l in g.layers:
        cum.append(cum[-1] * plan.layers[l].stride)
    for k, l in enumerate(g.layers):
        top, bottom, left, right = geom["halos"][k]
        ntop, nbot, nleft, nright = geom["halos"][k + 1]
        branch_io = tuple(
            (
                (top + r0 // cum[k] + bottom, left + c0 // cum[k] + right),
                (ntop + r0 // cum[k + 1] + nbot, nleft + c0 // cum[k + 1] + nright),
            )
            for r0 in runiq
            for c0 in cuniq
        )
        mask = (l != g.end) and any(geom["halos"][k + 1])
        out_off = (
            (
                static_table_lookup(_offsets(plan.tile_rows[l + 1]), i),
                static_table_lookup(_offsets(plan.tile_cols[l + 1]), j),
            )
            if mask
            else None
        )
        with obs.layer_scope(l):
            x = apply_layer_local_spec(
                x,
                params[l],
                plan.layers[l],
                branch=branch,
                branch_io=branch_io,
                out_halo=geom["halos"][k + 1],
                canon_out_hw=geom["eout"][k],
                map_out_hw=plan.map_hw[l + 1],
                out_off=out_off,
                row_axis=row_axis,
                col_axis=col_axis,
                batch_global=batch_global,
                batch_axis=batch_axis,
                mask_offmap=mask,
                backend=plan.backend,
                block_oh=plan.block_oh,
                inference=plan.inference,
            )
    return x


def _global_batch(
    local_batch: int, batch_axis: str | None, batch_global: int | None
) -> int:
    """Global batch for exact cross-tile BN statistics: explicit override, or
    local batch scaled by the batch mesh axis when one is present."""
    if batch_global is not None:
        return batch_global
    if batch_axis is None:
        return local_batch
    return local_batch * lax.axis_size(batch_axis)


def apply_stack_local(
    params: Sequence[dict],
    x: jax.Array,
    plan: StackPlan,
    *,
    row_axis: str = "th",
    col_axis: str = "tw",
    batch_axis: str | None = None,
    batch_global: int | None = None,
    wire: WireCtx | None = None,
) -> jax.Array:
    """Forward through all groups on one tile.  ``x``: (b, h/n, w/m, c).

    schedule="sync": eager 2-round halo exchange, then the group's layers.
    schedule="overlap": the group-lead layer goes through the packed-
    collective interior/boundary split (spatial.apply_group_lead_overlap),
    so its interior compute carries no data dependence on the halo
    ``ppermute``s; remaining group layers are unchanged (their inputs
    already depend on everything).

    Hybrid plans (DESIGN.md §7): at the first data-mode group the tile
    grid is resharded into batch shards (``reshard_spatial_to_data``) and
    every following layer runs on full, unhaloed maps with no collectives.
    The global batch for BN statistics is read off the *entry* shape, so
    it stays correct on both sides of the crossover.

    Non-uniform partitions: spatial groups route through the
    shape-specialized executor (``_apply_group_spec``, DESIGN.md §9) or -
    when ``plan.ragged_exec == "padded"`` - the padded-to-max fallback
    (``_apply_group_ragged``, DESIGN.md §8); both run the sync exchange
    regardless of schedule, and the crossover goes through the ragged
    reshard.  Uniform plans take exactly the pre-partition code path.

    Shortcuts (DESIGN.md §16): the outputs that later shortcuts read are
    kept, each with the halo it carries, and dropped after their last
    read.  A skip that is a group's input is kept as the exchanged (or,
    past the crossover, resharded) tile, so a shortcut inside the group
    crops it to its own halo.
    """
    bg = _global_batch(x.shape[0], batch_axis, batch_global)
    uniform = plan.is_uniform
    skips = SkipStore(plan.layers)
    skips.keep(-1, x, (0, 0, 0, 0))
    for gi, g in enumerate(plan.groups):
        if g.mode == "data":
            if gi == 0 or plan.groups[gi - 1].mode != "data":
                with obs.layer_scope(g.start), jax.named_scope(obs.RESHARD):
                    if uniform:
                        x = reshard_spatial_to_data(x, row_axis, col_axis, wire=wire)
                    else:
                        x = reshard_spatial_to_data_ragged(
                            x, row_axis, col_axis,
                            plan.tile_rows[g.start], plan.tile_cols[g.start],
                            wire=wire,
                        )
                skips.keep(g.start - 1, x, (0, 0, 0, 0))
            for l in g.layers:
                with obs.layer_scope(l):
                    x = apply_layer_data(
                        x,
                        params[l],
                        plan.layers[l],
                        map_out_hw=plan.map_hw[l + 1],
                        row_axis=row_axis,
                        col_axis=col_axis,
                        batch_global=bg,
                        backend=plan.backend,
                        batch_axis=batch_axis,
                        block_oh=plan.block_oh,
                        inference=plan.inference,
                        skip=skips.read(l)[0],
                    )
                skips.keep(l, x, (0, 0, 0, 0))
            continue
        if not uniform:
            group_fn = (
                _apply_group_spec if plan.ragged_exec == "spec" else _apply_group_ragged
            )
            x = group_fn(
                x, params, plan, gi,
                row_axis=row_axis, col_axis=col_axis,
                batch_axis=batch_axis, batch_global=bg,
                wire=wire,
            )
            continue
        layers = list(g.layers)
        if plan.schedule == "overlap" and any(plan.group_halos[gi]):
            lead = layers.pop(0)
            with obs.layer_scope(lead):
                x = apply_group_lead_overlap(
                    x,
                    params[lead],
                    plan.layers[lead],
                    halo=plan.group_halos[gi],
                    out_halo=plan.rem_halos[lead],
                    shard_out_hw=plan.shard_hw[lead + 1],
                    map_out_hw=plan.map_hw[lead + 1],
                    row_axis=row_axis,
                    col_axis=col_axis,
                    batch_global=bg,
                    mask_offmap=(lead != g.end),
                    backend=plan.backend,
                    batch_axis=batch_axis,
                    block_oh=plan.block_oh,
                    wire=wire,
                    inference=plan.inference,
                )
        else:
            with obs.layer_scope(g.start), jax.named_scope(obs.HALO):
                x = halo_exchange_2d(
                    x, plan.group_halos[gi], row_axis, col_axis, dims=(1, 2), wire=wire
                )
            skips.keep(g.start - 1, x, plan.group_halos[gi])
        for l in layers:
            skip, skip_halo = skips.read(l)
            with obs.layer_scope(l):
                x = apply_layer_local(
                    x,
                    params[l],
                    plan.layers[l],
                    out_halo=plan.rem_halos[l],
                    shard_out_hw=plan.shard_hw[l + 1],
                    map_out_hw=plan.map_hw[l + 1],
                    row_axis=row_axis,
                    col_axis=col_axis,
                    batch_global=bg,
                    mask_offmap=(l != g.end),
                    backend=plan.backend,
                    batch_axis=batch_axis,
                    block_oh=plan.block_oh,
                    inference=plan.inference,
                    skip=skip,
                    skip_halo=skip_halo,
                )
            skips.keep(l, x, plan.rem_halos[l])
    return x


# ---------------------------------------------------------------------------
# Mesh-level wrappers
# ---------------------------------------------------------------------------


def _pack_axis(a: jax.Array, sizes: tuple[int, ...], dim: int) -> jax.Array:
    """Global -> padded-tile layout along one axis: slice each tile's span
    and zero-pad it to the max tile size, so ``P(..., axis, ...)`` sharding
    hands every device its (padded) tile.  All-static; inverse of
    ``_unpack_axis``."""
    mx = max(sizes)
    if len(set(sizes)) == 1:
        return a
    parts = []
    off = 0
    for s in sizes:
        seg = lax.slice_in_dim(a, off, off + s, axis=dim)
        if s < mx:
            pad = [(0, 0)] * a.ndim
            pad[dim] = (0, mx - s)
            seg = jnp.pad(seg, pad)
        parts.append(seg)
        off += s
    return jnp.concatenate(parts, axis=dim)


def _unpack_axis(a: jax.Array, sizes: tuple[int, ...], dim: int) -> jax.Array:
    mx = max(sizes)
    if len(set(sizes)) == 1:
        return a
    parts = [
        lax.slice_in_dim(a, k * mx, k * mx + s, axis=dim)
        for k, s in enumerate(sizes)
    ]
    return jnp.concatenate(parts, axis=dim)


def _pack_grid(a, rows, cols, dims=(1, 2)):
    return _pack_axis(_pack_axis(a, rows, dims[0]), cols, dims[1])


def _unpack_grid(a, rows, cols, dims=(1, 2)):
    return _unpack_axis(_unpack_axis(a, rows, dims[0]), cols, dims[1])


def _shard_pack_axis(a: jax.Array, sizes: tuple[int, ...], axis_name: str, dim: int):
    """Shard-side pack (DESIGN.md §9): each device slices ITS tile's span
    out of the replicated global axis and zero-pads to the max tile size -
    an unrolled ``lax.switch`` over static slices, fusing the padded-tile
    layout transform into the shard_map boundary (no host-side padded
    global array, no ``dynamic_slice``)."""
    mx = max(sizes)

    def mk(off, s):
        def f(arr):
            seg = lax.slice_in_dim(arr, off, off + s, axis=dim)
            if s < mx:
                pad = [(0, 0)] * arr.ndim
                pad[dim] = (0, mx - s)
                seg = jnp.pad(seg, pad)
            return seg

        return f

    fns = [mk(off, s) for off, s in zip(_offsets(sizes), sizes)]
    if len(fns) == 1:
        return fns[0](a)
    return lax.switch(lax.axis_index(axis_name), fns, a)


def _shard_pack_grid(a, rows, cols, row_axis, col_axis, dims=(1, 2)):
    a = _shard_pack_axis(a, rows, row_axis, dims[0])
    return _shard_pack_axis(a, cols, col_axis, dims[1])


def _spec_core_loss(y, t_full, plan: StackPlan, loss_local, row_axis: str, col_axis: str):
    """Per-device core loss for spec plans (DESIGN.md §9): an unrolled
    switch over the n*m tiles statically slices this tile's valid output
    core and its span of the replicated global target, then runs
    ``loss_local`` on the TRUE extents - exact sums AND exact counts, with
    no validity masks and no count rescale (the padded executor's
    ``_ragged_count_scale`` is not needed)."""
    rows, cols = plan.tile_rows[-1], plan.tile_cols[-1]
    roffs, coffs = _offsets(rows), _offsets(cols)

    def mk(ri, cj):
        def f(y_, t_):
            yc = lax.slice_in_dim(
                lax.slice_in_dim(y_, 0, rows[ri], axis=1), 0, cols[cj], axis=2
            )
            tc = lax.slice_in_dim(
                lax.slice_in_dim(t_, roffs[ri], roffs[ri] + rows[ri], axis=1),
                coffs[cj], coffs[cj] + cols[cj], axis=2,
            )
            s, c = loss_local(yc, tc)
            return jnp.asarray(s, jnp.float32), jnp.asarray(c, jnp.float32)

        return f

    fns = [mk(ri, cj) for ri in range(len(rows)) for cj in range(len(cols))]
    if len(fns) == 1:
        return fns[0](y, t_full)
    branch = lax.axis_index(row_axis) * len(cols) + lax.axis_index(col_axis)
    return lax.switch(branch, fns, y, t_full)


def _ragged_count_scale(plan: StackPlan, row_axis: str, col_axis: str):
    """Fraction of a padded output tile that is valid, per device - scales
    ``loss_local``'s element count (pad slots hold y = t = 0, so the *sum*
    is already exact; only the count over-reads).  Requires the loss count
    to be proportional to the element count, as ``l2_loss_local``'s is."""
    rows = plan.tile_rows[-1]
    cols = plan.tile_cols[-1]
    oh = jnp.asarray(rows, jnp.float32)[lax.axis_index(row_axis)]
    ow = jnp.asarray(cols, jnp.float32)[lax.axis_index(col_axis)]
    return (oh * ow) / float(max(rows) * max(cols))


# ---------------------------------------------------------------------------
# Pipeline-tail executor (DESIGN.md §11): microbatch streaming over stage
# device subsets.  Everything here runs INSIDE shard_map.
# ---------------------------------------------------------------------------


def _pipeline_geometry(plan: StackPlan) -> dict:
    """Static geometry of a pipeline tail: the stage groups, devices per
    stage, the entry layer, and the padded *container* extents - one
    uniform (H, W, C) that covers every stage-boundary activation, so the
    inter-stage buffer and the per-stage ``lax.switch`` branches all share
    a single aval (each branch slices its TRUE extents statically)."""
    pg = [g for g in plan.groups if g.mode == "pipeline"]
    dims = []
    for g in pg:
        dims.append((*plan.map_hw[g.start], plan.layers[g.start].in_channels))
        dims.append((*plan.map_hw[g.end + 1], plan.layers[g.end].out_channels))
    return {
        "groups": pg,
        "n_stages": len(pg),
        "per_stage": (plan.n * plan.m) // len(pg),
        "pfirst": pg[0].start,
        "container": tuple(max(d[k] for d in dims) for k in range(3)),
    }


def _stage_shift(plan: StackPlan) -> tuple[str, int, int]:
    """How "flat index + devices-per-stage" decomposes into ONE axis-aligned
    shift on the (n x m) mesh: ("row"|"col", shift, axis_len).  Exists by
    the row-alignment feasibility rule (``feasible_stage_counts``): stage
    subsets are whole mesh rows (or the mesh is a single row/column)."""
    per = (plan.n * plan.m) // len(plan.stages)
    if plan.n == 1:
        return "col", per, plan.m
    if plan.m == 1:
        return "row", per, plan.n
    return "row", per // plan.m, plan.n


def pipeline_schedule_census(n_stages: int, microbatches: int) -> dict:
    """Occupancy census of the fill/drain schedule, from the same
    ``k = t - s`` arithmetic the executor's loss mask implements: stage
    ``s`` holds real (unmasked) work at tick ``t`` iff ``0 <= t - s < M``.
    ``bubble`` = idle slot fraction - the *measured* counterpart of the
    cost model's ``bubble_fraction(S, M) = (S-1)/(S-1+M)`` (they agree
    identically: idle = S*(S-1) slots out of S*(M+S-1))."""
    s_n, mb = n_stages, microbatches
    if s_n < 1 or mb < 1:
        raise ValueError(f"need n_stages >= 1 and microbatches >= 1; got {n_stages}, {microbatches}")
    ticks = mb + s_n - 1
    busy = sum(1 for t in range(ticks) for s in range(s_n) if 0 <= t - s < mb)
    idle = ticks * s_n - busy
    return {
        "stages": s_n,
        "microbatches": mb,
        "ticks": ticks,
        "busy_slots": busy,
        "idle_slots": idle,
        "bubble": idle / (ticks * s_n),
    }


def _apply_spatial_prefix(
    params, x, plan: StackPlan, *, row_axis, col_axis, bg, wire=None
):
    """The (possibly empty) spatial prefix of a pipeline plan - uniform
    sync executor only (pipeline plans forbid overlap and require uniform
    partitions, checked at build time)."""
    for gi, g in enumerate(plan.groups):
        if g.mode != "spatial":
            break
        with obs.layer_scope(g.start), jax.named_scope(obs.HALO):
            x = halo_exchange_2d(
                x, plan.group_halos[gi], row_axis, col_axis, dims=(1, 2), wire=wire
            )
        for l in g.layers:
            with obs.layer_scope(l):
                x = apply_layer_local(
                    x,
                    params[l],
                    plan.layers[l],
                    out_halo=plan.rem_halos[l],
                    shard_out_hw=plan.shard_hw[l + 1],
                    map_out_hw=plan.map_hw[l + 1],
                    row_axis=row_axis,
                    col_axis=col_axis,
                    batch_global=bg,
                    mask_offmap=(l != g.end),
                    backend=plan.backend,
                    batch_axis=None,
                    block_oh=plan.block_oh,
                )
    return x


def _check_pipeline_batch(plan: StackPlan, b_mu: int):
    per = (plan.n * plan.m) // len(plan.stages)
    if b_mu % per:
        raise ValueError(
            f"pipeline stage entry needs the per-microbatch batch ({b_mu}) "
            f"divisible by the devices per stage ({per}); pick "
            "batch/grad_accum so each microbatch spreads over one stage's "
            "device subset"
        )


def _make_pipeline_local(
    plan: StackPlan,
    loss_local,
    *,
    row_axis: str,
    col_axis: str,
    batch_global: int | None,
    microbatches: int,
):
    """Shard-local pipeline executor: (params, xs, ts) -> (loss_sum, count).

    ``xs``: (M, b_mu, h/n, w/m, C) spatially-sharded microbatches; ``ts``:
    (M, b_mu, H', W', C') replicated targets.  Runs ``T = M + S - 1``
    fill/drain ticks under ONE ``lax.scan`` (DESIGN.md §11).  Per tick:

    1. the whole mesh runs the spatial prefix on microbatch ``min(t, M-1)``
       (clamped replay past the fill: results are masked downstream);
    2. the entry gather all-gathers the tile grid into full maps and each
       device slices its *stage-rank* microbatch block (the pipeline
       analogue of ``reshard_spatial_to_data``; same AD-derived adjoint);
    3. stage-0 devices consume the entry, others their shifted buffer, and
       ONE ``lax.switch`` on the device's stage index runs its stage's
       layers (collective-free dense programs - BN is forbidden in stages);
    4. last-stage devices score microbatch ``t - (S-1)`` against its
       target block, masked to the valid window ``t >= S-1`` (fill/drain
       garbage and clamped replays get structurally zero loss, hence zero
       cotangents);
    5. the stage buffer ppermutes one stage forward (edge devices receive
       zeros - the no-wraparound shift convention).

    Differentiating this whole function per device and psumming the
    partials is exact: stage s's device processes microbatch ``t - s`` at
    tick ``t``, so every (sample, position) reaches a valid last-stage
    loss slot exactly once, and cross-stage/cross-tile dependencies flow
    through the transposed ppermutes and gathers."""
    geom = _pipeline_geometry(plan)
    pg = geom["groups"]
    n_st = geom["n_stages"]
    per_stage = geom["per_stage"]
    hc, wc, cc = geom["container"]
    mb = microbatches
    ticks = mb + n_st - 1
    h_out, w_out = plan.map_hw[-1]
    c_out = plan.layers[-1].out_channels
    axis_kind, shift, axis_len = _stage_shift(plan)
    shift_axis = row_axis if axis_kind == "row" else col_axis
    perm = [(k, k + shift) for k in range(axis_len - shift)]
    # The tick hand-off rides STATELESS compression both directions: an EF
    # residual inside the tick scan would have its cotangents summed across
    # ticks, breaking the one-residual-per-exchange bookkeeping (DESIGN.md
    # §12); the spatial prefix's exchanges are stateless for the same reason.
    codec = get_codec(plan.wire_codec)
    wire = None if codec is None else WireCtx(codec, EFBag("stateless"))

    def _to_container(x):
        return jnp.pad(
            x,
            ((0, 0), (0, hc - x.shape[1]), (0, wc - x.shape[2]), (0, cc - x.shape[3])),
        )

    def mk_branch(g, bg):
        hin, win = plan.map_hw[g.start]
        cin = plan.layers[g.start].in_channels

        def f(params, xc):
            x = xc[:, :hin, :win, :cin]
            for l in g.layers:
                with obs.layer_scope(l):
                    x = apply_layer_data(
                        x,
                        params[l],
                        plan.layers[l],
                        map_out_hw=plan.map_hw[l + 1],
                        row_axis=row_axis,
                        col_axis=col_axis,
                        batch_global=bg,
                        backend=plan.backend,
                        batch_axis=None,
                        block_oh=plan.block_oh,
                    )
            return _to_container(x)

        return f

    def local_fn(params, xs, ts):
        b_mu = xs.shape[1]
        bg = _global_batch(b_mu, None, batch_global)
        bp = b_mu // per_stage
        r = lax.axis_index(row_axis) * plan.m + lax.axis_index(col_axis)
        stage = r // per_stage
        rank = r % per_stage
        branches = [mk_branch(g, bg) for g in pg]

        def tick(carry, t):
            buf, s_acc, c_acc = carry
            k0 = jnp.clip(t, 0, mb - 1)
            x_mu = lax.dynamic_index_in_dim(xs, k0, axis=0, keepdims=False)
            h = _apply_spatial_prefix(
                params, x_mu, plan, row_axis=row_axis, col_axis=col_axis, bg=bg,
                wire=wire,
            )
            with obs.layer_scope(geom["pfirst"]), jax.named_scope(obs.RESHARD):
                h = lax.all_gather(h, row_axis, axis=1, tiled=True)
                h = lax.all_gather(h, col_axis, axis=2, tiled=True)
                entry = lax.dynamic_slice_in_dim(h, rank * bp, bp, axis=0)
            x_in = jnp.where(jnp.equal(stage, 0), _to_container(entry), buf)
            out = lax.switch(stage, branches, params, x_in)
            k_l = jnp.clip(t - (n_st - 1), 0, mb - 1)
            t_mu = lax.dynamic_index_in_dim(ts, k_l, axis=0, keepdims=False)
            t_blk = lax.dynamic_slice_in_dim(t_mu, rank * bp, bp, axis=0)
            y = out[:, :h_out, :w_out, :c_out]
            with jax.named_scope(obs.LOSS):
                s_l, c_l = loss_local(y, t_blk)
                s_l = jnp.asarray(s_l, jnp.float32)
                c_l = jnp.asarray(c_l, jnp.float32)
            valid = jnp.logical_and(jnp.equal(stage, n_st - 1), t >= n_st - 1)
            s_acc = s_acc + jnp.where(valid, s_l, 0.0)
            c_acc = c_acc + jnp.where(valid, c_l, 0.0)
            buf = wire_shift(out, shift_axis, perm, wire)
            return (buf, s_acc, c_acc), None

        buf0 = jnp.zeros((bp, hc, wc, cc), xs.dtype)
        (_, s_tot, c_tot), _ = lax.scan(
            tick,
            (buf0, jnp.float32(0.0), jnp.float32(0.0)),
            jnp.arange(ticks),
        )
        return s_tot, c_tot

    return local_fn


def _stateless_wire(plan: StackPlan) -> WireCtx | None:
    """Wire ctx for paths with no EF carry (single-shot forward/loss, the
    pipeline tick): residuals are zeros constants, so compression is
    stateless.  ``None`` for codec=none - every call site then runs the
    legacy collective byte-for-byte."""
    codec = get_codec(plan.wire_codec)
    return None if codec is None else WireCtx(codec, EFBag("stateless"))


def make_tiled_forward(
    plan: StackPlan,
    mesh: Mesh,
    *,
    row_axis: str = "th",
    col_axis: str = "tw",
    batch_axis: str | None = None,
    batch_global: int | None = None,
):
    """shard_map'd forward: (params, x_global) -> y_global.

    Params replicated (paper: every device holds a full filter copy);
    activations sharded (batch?, H/th, W/tw, C).  A hybrid plan's output
    leaves in data layout instead: full maps with the batch dim sharded
    over (batch_axis?, row_axis, col_axis) - the assembly order of
    ``reshard_spatial_to_data``'s batch blocks.

    Ragged plans keep the caller-facing contract - global arrays in,
    global arrays out - partition-independent.  Spec plans (DESIGN.md §9)
    bind the input spatially-unsharded and pack INSIDE the shard boundary
    (``_shard_pack_grid``); padded-fallback plans pack on the host
    (``_pack_grid``).  Both unpack a spatial output on the host; uniform
    plans return the bare shard_map'd function, jaxpr-identical to the
    pre-partition executor.
    """
    if plan.stages:
        raise ValueError(
            "pipeline plans have no single-shot forward layout: outputs "
            "live on the last stage's devices only, one microbatch per "
            "tick; use make_tiled_loss / make_deferred_grad_step (or a "
            "non-pipeline plan for inference)"
        )
    spec_exec = not plan.is_uniform and plan.ragged_exec == "spec"
    aspec = (
        P(batch_axis, None, None, None)
        if spec_exec
        else P(batch_axis, row_axis, col_axis, None)
    )
    out_spec = _out_spec(plan, row_axis, col_axis, batch_axis)
    local = functools.partial(
        apply_stack_local,
        plan=plan,
        row_axis=row_axis,
        col_axis=col_axis,
        batch_axis=batch_axis,
        batch_global=batch_global,
        wire=_stateless_wire(plan),
    )

    def fn(params, x):
        if spec_exec:
            x = _shard_pack_grid(
                x, plan.tile_rows[0], plan.tile_cols[0], row_axis, col_axis
            )
        return local(params, x)

    mapped = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(), aspec),
        out_specs=out_spec,
        check_vma=False,
    )
    if plan.is_uniform:
        return mapped

    def fwd(params, x):
        if not spec_exec:
            x = _pack_grid(x, plan.tile_rows[0], plan.tile_cols[0])
        y = mapped(params, x)
        if plan.crossover is None:
            y = _unpack_grid(y, plan.tile_rows[-1], plan.tile_cols[-1])
        return y

    return fwd


def _check_not_inference(plan: StackPlan, what: str) -> None:
    if plan.inference:
        raise ValueError(
            f"{what} is a training entry point, but the plan is forward-only "
            "(inference=True): training BN needs cross-device batch "
            "statistics the serve executor deliberately has no collectives "
            "for; build a training plan (inference=False) instead"
        )


def make_tiled_infer(
    plan: StackPlan,
    mesh: Mesh,
    *,
    row_axis: str = "th",
    col_axis: str = "tw",
    batch_axis: str | None = None,
):
    """The serve step (DESIGN.md §13): shard_map'd forward-only
    ``(params, x_global) -> y_global`` for an inference plan.

    Structurally this is ``make_tiled_forward`` on a plan whose BN layers
    read frozen ``bn_mean``/``bn_var`` statistics (``freeze_bn_stats``)
    instead of psum'ing batch statistics - so the traced step contains *no*
    training-only collective: no BN psum, no batch-end gradient psum, no
    deferred-grad scan.  The only collectives left are the forward halo
    ``ppermute``s and (for hybrid plans) the crossover all-gather - the
    irreducible SPMD data movement.  ``scripts/check_serve.py`` asserts
    this on the jaxpr.

    Requires ``build_stack_plan(..., inference=True)`` (or
    ``plan.inference_twin()``): refusing training plans here keeps the
    train/serve BN semantics an explicit plan-time choice rather than a
    silent numeric drift."""
    if not plan.inference:
        raise ValueError(
            "make_tiled_infer needs a forward-only plan: build with "
            "build_stack_plan(..., inference=True) or take "
            "plan.inference_twin(); training plans psum BN batch statistics "
            "and must go through make_tiled_forward/make_tiled_loss"
        )
    return make_tiled_forward(
        plan, mesh,
        row_axis=row_axis, col_axis=col_axis,
        batch_axis=batch_axis,
    )


def _out_spec(plan: StackPlan, row_axis: str, col_axis: str, batch_axis: str | None):
    """Output layout of the executor: spatially sharded for all-spatial
    plans; batch-sharded full maps after a crossover."""
    if plan.crossover is None:
        return P(batch_axis, row_axis, col_axis, None)
    axes = tuple(a for a in (batch_axis, row_axis, col_axis) if a is not None)
    return P(axes, None, None, None)


def _check_data_batch(plan: StackPlan, mesh: Mesh, batch: int, batch_axis: str | None):
    """Named trace-time error for hybrid plans whose per-microbatch batch
    cannot spread over the tile grid - raised before shard_map's generic
    in_spec divisibility message can fire on the batch-sharded target."""
    if plan.crossover is None:
        return
    if batch_axis is not None:
        bsize = dict(zip(mesh.axis_names, mesh.devices.shape))[batch_axis]
        if batch % bsize:
            return   # let shard_map report the batch-axis mismatch itself
        batch = batch // bsize
    t = plan.n * plan.m
    if batch % t:
        raise ValueError(
            f"data-mode batch split needs the per-microbatch batch ({batch}) "
            f"divisible by the tile count ({plan.n}x{plan.m}={t})"
        )


def make_tiled_loss(
    plan: StackPlan,
    mesh: Mesh,
    loss_local,
    *,
    row_axis: str = "th",
    col_axis: str = "tw",
    batch_axis: str | None = None,
    batch_global: int | None = None,
):
    """shard_map'd scalar loss: mean over the *global* output map.

    loss_local(y_local, t_local) -> (local_sum, local_count).  The cross-tile
    psum makes the scalar identical to the untiled loss, so jax.grad of this
    function reproduces the paper's tiled backward pass exactly (including
    the weight-gradient partial-sum aggregation, inserted by shard_map
    transposition for the replicated params operand).

    Hybrid plans: the *target* is bound with the executor's data-side
    out-spec (batch sharded over the tile axes, full maps) instead of the
    spatial aspec, so ``loss_local`` sees matching y/t layouts with no
    extra collective - shard_map hands each device exactly the batch block
    ``reshard_spatial_to_data`` assigns it.  This also keeps grid-ragged
    output extents trainable (the data tail is exempt from tile-grid
    divisibility, and so must be its target).  Each (sample, position) is
    still owned by exactly one device, so the psum'd mean is unchanged.

    Pipeline plans (DESIGN.md §11) run the tick executor with M=1 (pure
    fill/drain - every batch streams through the stages once); the target
    is bound replicated and each last-stage device scores its stage-rank
    block, so the psum'd scalar still equals the untiled loss exactly.
    """
    _check_not_inference(plan, "make_tiled_loss")
    if plan.stages:
        if batch_axis is not None:
            raise ValueError(
                "pipeline plans stream microbatch blocks over stage ranks; "
                "batch_axis must be None"
            )
        local = _make_pipeline_local(
            plan, loss_local, row_axis=row_axis, col_axis=col_axis,
            batch_global=batch_global, microbatches=1,
        )
        axes = (row_axis, col_axis)

        def pfn(params, xs, ts):
            s, c = local(params, xs, ts)
            with jax.named_scope(obs.LOSS):
                return lax.psum(s, axes) / lax.psum(c, axes)

        mapped = jax.shard_map(
            pfn,
            mesh=mesh,
            in_specs=(P(), P(None, None, row_axis, col_axis, None), P()),
            out_specs=P(),
            check_vma=False,
        )

        def loss(params, x, target):
            _check_pipeline_batch(plan, x.shape[0])
            return mapped(params, x[None], target[None])

        return loss

    spec_exec = not plan.is_uniform and plan.ragged_exec == "spec"
    aspec = (
        P(batch_axis, None, None, None)
        if spec_exec
        else P(batch_axis, row_axis, col_axis, None)
    )
    if spec_exec and plan.crossover is None:
        # Spec plans bind the target replicated-spatial too; the core-loss
        # switch slices each tile's span statically (DESIGN.md §9).
        tspec = P(batch_axis, None, None, None)
    else:
        tspec = _out_spec(plan, row_axis, col_axis, batch_axis)
    axes = (row_axis, col_axis) if batch_axis is None else (batch_axis, row_axis, col_axis)
    ragged_out = not plan.is_uniform and plan.crossover is None and not spec_exec
    wire = _stateless_wire(plan)

    def fn(params, x, target):
        if spec_exec:
            x = _shard_pack_grid(
                x, plan.tile_rows[0], plan.tile_cols[0], row_axis, col_axis
            )
        y = apply_stack_local(
            params, x, plan,
            row_axis=row_axis, col_axis=col_axis,
            batch_axis=batch_axis, batch_global=batch_global,
            wire=wire,
        )
        with jax.named_scope(obs.LOSS):
            if spec_exec and plan.crossover is None:
                s, c = _spec_core_loss(y, target, plan, loss_local, row_axis, col_axis)
            else:
                s, c = loss_local(y, target)
                if ragged_out:
                    # pad slots hold y = t = 0 (executor mask / packed target), so
                    # the sum is exact; rescale the count to valid elements only.
                    c = c * _ragged_count_scale(plan, row_axis, col_axis)
            s = lax.psum(s, axes)
            c = lax.psum(c, axes)
            return s / c

    mapped = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(), aspec, tspec),
        out_specs=P(),
        check_vma=False,
    )

    def loss(params, x, target):
        _check_data_batch(plan, mesh, x.shape[0], batch_axis)
        if not plan.is_uniform and not spec_exec:
            x = _pack_grid(x, plan.tile_rows[0], plan.tile_cols[0])
            if plan.crossover is None:
                target = _pack_grid(target, plan.tile_rows[-1], plan.tile_cols[-1])
        return mapped(params, x, target)

    return loss


def make_deferred_grad_step(
    plan: StackPlan,
    mesh: Mesh,
    loss_local,
    *,
    row_axis: str = "th",
    col_axis: str = "tw",
    batch_axis: str | None = None,
    batch_global: int | None = None,
    microbatches: int = 1,
):
    """Paper §4.1 deferred weight aggregation: per-tile partial weight grads
    accumulate locally across ``microbatches`` samples; ONE psum at the end
    of the batch produces the final weight gradients.

    Returns (loss_mean, grads) with grads already aggregated.  x/target are
    (microbatches, b, H, W, C) globally.

    Hybrid plans compose transparently: each microbatch's backward runs the
    adjoint reshard (reduce-scatter + zero-padded batch scatter, derived by
    AD) so the accumulated partials are always in the params' (replicated)
    layout - the single batch-end psum, and therefore int8-EF compression
    and microbatching, are untouched by the crossover.  The target is bound
    with the data-side layout (batch sharded over the tile axes, full maps)
    like ``make_tiled_loss``.

    Pipeline plans (DESIGN.md §11) reuse ``microbatches`` as the pipeline
    depth M: instead of a scan over independent microbatch grad steps, ONE
    fill/drain tick scan streams all M microbatches through the stages and
    is differentiated as a whole (cotangents flow backward through the
    transposed inter-stage ppermutes).  The batch-end psum tail - and
    therefore the int8-EF weight path - is identical to the non-pipeline
    executor's.
    """
    _check_not_inference(plan, "make_deferred_grad_step")
    if plan.stages:
        if batch_axis is not None:
            raise ValueError(
                "pipeline plans stream microbatch blocks over stage ranks; "
                "batch_axis must be None"
            )
        local = _make_pipeline_local(
            plan, loss_local, row_axis=row_axis, col_axis=col_axis,
            batch_global=batch_global, microbatches=microbatches,
        )
        pipe_axes = (row_axis, col_axis)

        def pfn(params, xs, ts):
            (s_tot, c_tot), g = jax.value_and_grad(local, has_aux=True)(
                params, xs, ts
            )
            # The single end-of-batch aggregation, shared with the
            # non-pipeline path (partial sums -> final grads).
            with jax.named_scope(obs.GRAD_SUM):
                cnt_g = lax.psum(c_tot, pipe_axes)
                grads = jax.tree.map(lambda a: lax.psum(a, pipe_axes) / cnt_g, g)
                loss = lax.psum(s_tot, pipe_axes) / cnt_g
            return loss, grads

        pmapped = jax.shard_map(
            pfn,
            mesh=mesh,
            in_specs=(P(), P(None, None, row_axis, col_axis, None), P()),
            out_specs=(P(), P()),
            check_vma=False,
        )

        def pstep(params, xs, ts):
            if xs.shape[0] != microbatches:
                raise ValueError(
                    f"pipeline grad step built for microbatches={microbatches}; "
                    f"got {xs.shape[0]} microbatches"
                )
            _check_pipeline_batch(plan, xs.shape[1])
            return pmapped(params, xs, ts)

        return pstep

    spec_exec = not plan.is_uniform and plan.ragged_exec == "spec"
    aspec = (
        P(None, batch_axis, None, None, None)
        if spec_exec
        else P(None, batch_axis, row_axis, col_axis, None)
    )
    ospec = _out_spec(plan, row_axis, col_axis, batch_axis)
    if spec_exec and plan.crossover is None:
        tspec = P(None, batch_axis, None, None, None)
    else:
        tspec = P(None, *ospec)
    tile_axes = (row_axis, col_axis) if batch_axis is None else (batch_axis, row_axis, col_axis)
    ragged_out = not plan.is_uniform and plan.crossover is None and not spec_exec

    codec = get_codec(plan.wire_codec)

    def local_loss(params, x, t, wire=None):
        if spec_exec:
            x = _shard_pack_grid(
                x, plan.tile_rows[0], plan.tile_cols[0], row_axis, col_axis
            )
        y = apply_stack_local(
            params, x, plan,
            row_axis=row_axis, col_axis=col_axis,
            batch_axis=batch_axis, batch_global=batch_global,
            wire=wire,
        )
        with jax.named_scope(obs.LOSS):
            if spec_exec and plan.crossover is None:
                s, c = _spec_core_loss(y, t, plan, loss_local, row_axis, col_axis)
            else:
                s, c = loss_local(y, t)
                if ragged_out:
                    c = c * _ragged_count_scale(plan, row_axis, col_axis)
        # Divide by the *global* count; the cross-tile sum is deferred to the
        # gradient aggregation (linearity), matching the paper's schedule.
        return s, c

    if codec is None:

        def fn(params, xs, ts):
            def step(carry, xt):
                acc, loss_acc, cnt_acc = carry
                x, t = xt
                (s, c), g = jax.value_and_grad(local_loss, has_aux=True)(params, x, t)

                def _upd(a, b):
                    return a + b

                with jax.named_scope(obs.GRAD_SUM):
                    acc = jax.tree.map(_upd, acc, g)
                return (acc, loss_acc + s, cnt_acc + c), None

            zeros = jax.tree.map(jnp.zeros_like, params)
            (acc, loss_sum, cnt), _ = lax.scan(step, (zeros, 0.0, 0.0), (xs, ts))
            # The single end-of-batch aggregation (partial sums -> final grads).
            with jax.named_scope(obs.GRAD_SUM):
                cnt_g = lax.psum(cnt, tile_axes)
                grads = jax.tree.map(lambda a: lax.psum(a, tile_axes) / cnt_g, acc)
                loss = lax.psum(loss_sum, tile_axes) / cnt_g
            return loss, grads

    else:
        # Compressed wire: the backward cotangents of every recurring
        # exchange ride error feedback, and the residual buffers are
        # EXPLICIT scan carry - taken apart into a flat tuple whose layout
        # is discovered by an abstract probe (jax.eval_shape adds no ops),
        # handed to each microbatch's trace in deterministic order, and
        # returned as the gradient w.r.t. the residual argument by the
        # custom-VJP shifts (DESIGN.md §12).  Residuals accumulate across
        # the microbatches of one batch and start at zero each step.

        def local_loss_ef(params, ef, x, t):
            bag = EFBag("buffers", ef)
            return local_loss(params, x, t, wire=WireCtx(codec, bag))

        def fn(params, xs, ts):
            bag_c = EFBag("collect")

            def probe(p, x, t):
                return local_loss(p, x, t, wire=WireCtx(codec, bag_c))[0]

            jax.eval_shape(
                probe,
                params,
                jax.ShapeDtypeStruct(xs.shape[1:], xs.dtype),
                jax.ShapeDtypeStruct(ts.shape[1:], ts.dtype),
            )
            ef0 = tuple(jnp.zeros(s, d) for s, d in bag_c.shapes)

            def step(carry, xt):
                acc, ef, loss_acc, cnt_acc = carry
                x, t = xt
                (s, c), (g, new_ef) = jax.value_and_grad(
                    local_loss_ef, argnums=(0, 1), has_aux=True
                )(params, ef, x, t)
                with jax.named_scope(obs.GRAD_SUM):
                    acc = jax.tree.map(lambda a, b: a + b, acc, g)
                return (acc, new_ef, loss_acc + s, cnt_acc + c), None

            zeros = jax.tree.map(jnp.zeros_like, params)
            (acc, _, loss_sum, cnt), _ = lax.scan(
                step, (zeros, ef0, 0.0, 0.0), (xs, ts)
            )
            with jax.named_scope(obs.GRAD_SUM):
                cnt_g = lax.psum(cnt, tile_axes)
                grads = jax.tree.map(lambda a: lax.psum(a, tile_axes) / cnt_g, acc)
                loss = lax.psum(loss_sum, tile_axes) / cnt_g
            return loss, grads

    mapped = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(), aspec, tspec),
        out_specs=(P(), P()),
        check_vma=False,
    )

    def step(params, xs, ts):
        _check_data_batch(plan, mesh, xs.shape[1], batch_axis)
        if not plan.is_uniform and not spec_exec:
            xs = _pack_grid(xs, plan.tile_rows[0], plan.tile_cols[0], dims=(2, 3))
            if plan.crossover is None:
                ts = _pack_grid(ts, plan.tile_rows[-1], plan.tile_cols[-1], dims=(2, 3))
        return mapped(params, xs, ts)

    return step


# ---------------------------------------------------------------------------
# Reference (untiled) counterparts for testing
# ---------------------------------------------------------------------------


def reference_forward(params, x, plan: StackPlan):
    return stack_reference(x, params, plan.layers, inference=plan.inference)


def reference_loss(params, x, target, plan: StackPlan, loss_local):
    y = reference_forward(params, x, plan)
    s, c = loss_local(y, target)
    return s / c
