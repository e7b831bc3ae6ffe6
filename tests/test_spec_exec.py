"""Plan-time geometry of the shape-specialized ragged executor (DESIGN.md §9).

Multi-device executor exactness and the jaxpr assertions (no dynamic
slicing, true-extent convs, per-shape conv programs) live in
scripts/check_pipeline.py (subprocess, 4 fake devices - see
tests/test_spmd.py); this file covers the pure single-device pieces: the
per-axis shape dedup, the balancer's halo floor, the specialization-
overhead cost term, and ``schedule="auto"`` resolution.
"""
import dataclasses
import itertools

import jax
import pytest

from repro import compat
from repro.core.fusion import build_stack_plan
from repro.core.grouping import (
    ClusterSpec,
    PI3_PROFILE,
    _bounds_makespan,
    _min_extent_floor,
    balance_bounds,
    cluster_partition,
    parse_cluster_spec,
    profile_cost,
)
from repro.core.spatial import LayerDef
from repro.core.tiling import TilePartition, bounds_sizes, dedup_axis_shapes, no_grouping


# ---------------------------------------------------------------------------
# dedup_axis_shapes
# ---------------------------------------------------------------------------


def test_dedup_axis_shapes_basic():
    table, uniq = dedup_axis_shapes((4, 3, 4, 3))
    assert uniq == (4, 3)
    assert table == (0, 1, 0, 1)
    # the table indexes back into uniq exactly
    assert tuple(uniq[b] for b in table) == (4, 3, 4, 3)


def test_dedup_axis_shapes_uniform_and_distinct():
    assert dedup_axis_shapes((5, 5, 5)) == ((0, 0, 0), (5,))
    assert dedup_axis_shapes((7, 3, 5)) == ((0, 1, 2), (7, 3, 5))


def test_dedup_keeps_2x2_hetero_at_two_programs_per_axis():
    # The ISSUE's headline case: a 2/62-style row split must dedup to 2 row
    # programs (not 4) - the per-axis key is the size alone.
    table, uniq = dedup_axis_shapes((2, 62))
    assert len(uniq) == 2 and table == (0, 1)
    ctab, cuniq = dedup_axis_shapes((32, 32))
    assert len(cuniq) == 1 and ctab == (0, 0)
    # total distinct (row, col) programs = 2 * 1
    assert len(uniq) * len(cuniq) == 2


# ---------------------------------------------------------------------------
# balance_bounds min_size floor
# ---------------------------------------------------------------------------


def _brute_best(extent_hw, cluster, floor_r, floor_c):
    """Exhaustive 2x2 optimum under per-axis floors."""
    h, w = extent_hw
    flops = [[p.flops for p in row] for row in cluster.grid]
    best = None
    for rk in range(floor_r, h - floor_r + 1):
        for ck in range(floor_c, w - floor_c + 1):
            cost = _bounds_makespan((0, rk, h), (0, ck, w), flops)
            if best is None or cost < best:
                best = cost
    return best


def test_balance_bounds_floor_optimal_2x2():
    cluster = parse_cluster_spec("pi3x3+jetson", 2, 2)
    for extent, floor in (((9, 11), 3), ((16, 16), 5)):
        rb, cb = balance_bounds(extent, cluster, min_size=floor)
        assert min(bounds_sizes(rb)) >= floor
        assert min(bounds_sizes(cb)) >= floor
        flops = [[p.flops for p in row] for row in cluster.grid]
        got = _bounds_makespan(rb, cb, flops)
        assert got == pytest.approx(_brute_best(extent, cluster, floor, floor))


def test_balance_bounds_floor_caps_extreme_skew():
    # A 1000x FLOPs ratio wants to give the slow device a sliver; the floor
    # must hold it at min_size anyway.
    fast = dataclasses.replace(PI3_PROFILE, name="fast-dev", flops=PI3_PROFILE.flops * 1000)
    cluster = ClusterSpec(name="skew", grid=((PI3_PROFILE, fast), (fast, fast)))
    rb, cb = balance_bounds((64, 64), cluster, min_size=4)
    assert min(bounds_sizes(rb)) >= 4
    assert min(bounds_sizes(cb)) >= 4
    # without a floor the same cluster does emit a thinner tile
    rb0, cb0 = balance_bounds((64, 64), cluster)
    assert min(min(bounds_sizes(rb0)), min(bounds_sizes(cb0))) < 4


# ---------------------------------------------------------------------------
# _min_extent_floor + cluster_partition integration
# ---------------------------------------------------------------------------


def test_min_extent_floor_values():
    conv3 = LayerDef(3, 1, 4, 4)            # halo (1, 1)
    conv7 = LayerDef(7, 1, 4, 4)            # halo (3, 3)
    pool2 = LayerDef(2, 2, 4, 4, pool=True)  # halo (0, 0), stride 2
    assert _min_extent_floor([conv3], 1) == 1
    assert _min_extent_floor([conv7], 1) == 3
    # a stride-2 pool between balance extent and the conv halves the
    # pull-back: ceil(3 / 2) = 2
    assert _min_extent_floor([conv7, pool2], 2) == 2
    assert _min_extent_floor([], 0) == 1


def test_cluster_partition_respects_per_layer_halos():
    # Brute force over every spatial layer of a big-kernel stack on an
    # extreme cluster: no tile may be thinner than that layer's halo (else
    # the plan-time "halo exceeds the smallest tile" error fires).
    fast = dataclasses.replace(PI3_PROFILE, name="fast-dev", flops=PI3_PROFILE.flops * 1000)
    cluster = ClusterSpec(name="skew", grid=((PI3_PROFILE, fast), (fast, fast)))
    layers = [LayerDef(7, 1, 3, 4), LayerDef(5, 1, 4, 4), LayerDef(3, 1, 4, 4)]
    part = cluster_partition((48, 48), layers, cluster, None)
    plan = build_stack_plan((48, 48), layers, 2, 2, hw=cluster, partition=part)
    assert not plan.is_uniform
    for l, layer in enumerate(layers):
        lo, hi = layer.halo
        need = max(lo, hi)
        assert min(plan.tile_rows[l]) >= need, (l, plan.tile_rows[l])
        assert min(plan.tile_cols[l]) >= need, (l, plan.tile_cols[l])
    # the derived default (partition=None) goes through the same floor
    plan2 = build_stack_plan((48, 48), layers, 2, 2, hw=cluster)
    assert plan2.partition == part


# ---------------------------------------------------------------------------
# specialization-overhead cost term
# ---------------------------------------------------------------------------


def test_spec_pad_cost_term_isolated():
    # 1x2 cluster, slow device (bottleneck) first, one 1x1 conv on a 1x8
    # strip (no halo, no boundary bytes, channels 1) - the modelled compute
    # is exactly predictable per device: 3 passes x true-extent MACs plus
    # SPEC_PAD_MACS x (canonical - true extent).  A 2/6 split hands the slow
    # device 2 valid columns repadded to the canonical 6, so its makespan
    # must carry the pad charge (3*2 + 2*(6-2) = 14 MACs, not 6); the
    # uniform 4/4 split has zero pad term (3*4 = 12 MACs exactly).
    slow = PI3_PROFILE
    fast = dataclasses.replace(PI3_PROFILE, name="fast-dev", flops=slow.flops * 1000)
    cluster = ClusterSpec(name="pair", grid=((slow, fast),))
    layer = [LayerDef(1, 1, 1, 1)]
    groups = tuple(no_grouping(1))
    sync = 2 * cluster.max_sync_latency    # constant in both partitions
    even = profile_cost(
        (1, 8), layer, groups, 1, 2, cluster,
        partition=TilePartition((0, 1), (0, 4, 8)),
    )
    skew = profile_cost(
        (1, 8), layer, groups, 1, 2, cluster,
        partition=TilePartition((0, 1), (0, 2, 8)),
    )
    assert even["compute"] == pytest.approx(12 / slow.flops)
    assert skew["compute"] == pytest.approx(14 / slow.flops)   # 6 conv + 8 pad
    assert even["sync"] == pytest.approx(sync)
    # without the pad term the slow device would model 6/flops - less than
    # half the charged figure, which is what hid the measured gap (ISSUE 6)
    assert skew["compute"] > 2 * (6 / slow.flops)


# ---------------------------------------------------------------------------
# schedule="auto" + plan knobs
# ---------------------------------------------------------------------------


def test_auto_schedule_resolves_sync_on_cpu():
    layers = [LayerDef(3, 1, 3, 4)]
    plan = build_stack_plan((8, 8), layers, 1, 1, schedule="auto")
    assert plan.schedule == "sync"          # host CPU cannot hide collectives
    cluster = parse_cluster_spec("pi3x3+jetson", 2, 2)
    plan2 = build_stack_plan((32, 32), layers, 2, 2, schedule="auto", hw=cluster)
    assert plan2.schedule == "sync"         # hetero clusters always sync


def test_plan_knob_validation():
    layers = [LayerDef(3, 1, 3, 4)]
    with pytest.raises(ValueError, match="ragged_exec"):
        build_stack_plan((8, 8), layers, 1, 1, ragged_exec="nope")
    with pytest.raises(ValueError, match="schedule"):
        build_stack_plan((8, 8), layers, 1, 1, schedule="nope")
    plan = build_stack_plan((7, 7), layers, 1, 1, ragged_exec="padded")
    assert plan.ragged_exec == "padded"
    assert build_stack_plan((7, 7), layers, 1, 1).ragged_exec == "spec"


def test_overlap_compat_helpers():
    assert not compat.overlap_supported("cpu")
    assert compat.overlap_supported("gpu") and compat.overlap_supported("tpu")
    env = {}
    added = compat.enable_overlap_xla_flags(env)
    assert added == list(compat.XLA_GPU_OVERLAP_FLAGS)
    assert env["XLA_FLAGS"].split() == list(compat.XLA_GPU_OVERLAP_FLAGS)
    # idempotent
    assert compat.enable_overlap_xla_flags(env) == []
    # explicit user choices win: a flag whose key is present is not re-added
    env2 = {"XLA_FLAGS": "--xla_gpu_enable_async_collectives=false"}
    added2 = compat.enable_overlap_xla_flags(env2)
    assert "--xla_gpu_enable_async_collectives=true" not in added2
    assert len(added2) == len(compat.XLA_GPU_OVERLAP_FLAGS) - 1
    assert "=false" in env2["XLA_FLAGS"]


def test_compile_cache_dir(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and nothing else is set; without it
    the cache goes to one fixed, git-ignored directory of the checkout."""
    root = compat.COMPILE_CACHE_DIR.parent
    assert (root / "pyproject.toml").exists()
    assert f"{compat.COMPILE_CACHE_DIR.name}/" in (root / ".gitignore").read_text().split()
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compat.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert compat.enable_compile_cache() == str(compat.COMPILE_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(compat.COMPILE_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
