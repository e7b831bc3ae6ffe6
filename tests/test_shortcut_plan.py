"""Residual shortcuts (YOLOv3's Darknet-53, DESIGN.md §16) at plan time: the
layer list, the group-edge rule and the refusals that name the shortcut, the
planner's DP around residual units, the skip charge of
``peak_device_memory``, and the launcher's ``--arch yolov3-tiled``."""
import jax
import pytest

from repro.core.fusion import build_stack_plan
from repro.core.grouping import (
    TPU_V5E_PROFILE,
    optimize_grouping,
    peak_device_memory,
    profile_cost,
    shortcut_group_error,
)
from repro.core.spatial import LayerDef, shortcut_layer, shortcut_sources
from repro.core.tiling import Group, TilePartition, no_grouping
from repro.models.yolo import yolov2_16_layers
from repro.models.yolov3 import make_yolov3_tiled_arch, yolov3_62_layers

V3 = yolov3_62_layers()
V3_12 = V3[:12]


def test_the_62_layers_are_darknets_sections():
    assert len(V3) == 62
    assert sum(l.is_conv for l in V3) == 43
    assert sorted(shortcut_sources(V3).items())[:3] == [(4, 1), (8, 5), (11, 8)]
    assert len(shortcut_sources(V3)) == 19
    assert [i for i, l in enumerate(V3) if l.stride == 2] == [1, 5, 12, 37]
    assert V3[1].halo == (1, 0)                       # 3x3 stride 2, pad 1
    assert V3[4].halo == (0, 0) and V3[4].act == "linear"
    weights = sum(l.kernel ** 2 * l.in_channels * l.out_channels for l in V3 if l.is_conv)
    assert weights == 14_859_104
    plan = build_stack_plan((416, 416), V3, 1, 1)
    assert plan.map_hw[-1] == (26, 26) and V3[-1].out_channels == 512


def test_a_unit_fuses_and_a_cut_inside_it_is_refused():
    ok = [Group(0, 1), Group(2, 4), Group(5, 5), Group(6, 8), Group(9, 11)]
    plan = build_stack_plan((64, 64), V3_12, 2, 2, ok)
    assert plan.group_halos[1] == (1, 1, 1, 1)        # the unit's 3x3 conv
    # a group that starts inside unit 2..4 and carries a halo past its shortcut
    bad = [Group(0, 2), Group(3, 6), Group(7, 11)]
    with pytest.raises(ValueError, match=r"shortcut layer 4 \(from=-3\).*group \(3, 6\)"):
        build_stack_plan((64, 64), V3_12, 2, 2, bad)
    # a cut inside a unit where the shortcut needs no halo: the default plan
    build_stack_plan((64, 64), V3_12, 2, 2, no_grouping(12))
    assert shortcut_group_error(V3_12, 3, 4) is None
    assert "shortcut layer 4" in shortcut_group_error(V3_12, 3, 6)


@pytest.mark.parametrize("kw,match", [
    (dict(crossover=7), r"crossover at layer 7 falls inside .* shortcut layer 8"),
    (dict(schedule="overlap"), r"shortcut layer 4 .*overlap schedule"),
    (dict(partition=TilePartition((0, 16, 64), (0, 32, 64))), r"shortcut layer 4 .*ragged"),
    (dict(groups="auto", pipeline=2, hw=TPU_V5E_PROFILE), r"shortcut layer 4 .*pipeline"),
], ids=["crossover-inside-unit", "overlap", "ragged", "pipeline"])
def test_plans_the_shortcut_cannot_run_are_refused(kw, match):
    groups = kw.pop("groups", None)
    with pytest.raises(ValueError, match=match):
        build_stack_plan((64, 64), V3_12, 2, 2, groups, **kw)


def test_pipeline_groups_from_a_manifest_are_refused():
    groups = [Group(0, 5), Group(6, 8, "pipeline"), Group(9, 11, "pipeline")]
    layers = yolov3_62_layers(batch_norm=False)[:12]   # BN stays out of stages
    with pytest.raises(ValueError, match=r"shortcut layer 4 .*pipeline"):
        build_stack_plan((64, 64), layers, 2, 2, groups)


def test_crossover_at_a_units_edge_is_planned():
    plan = build_stack_plan((64, 64), V3_12, 2, 2, crossover=9)
    assert plan.crossover == 9 and plan.groups[-1].mode == "data"
    plan = build_stack_plan((64, 64), V3_12, 2, 2, crossover="auto", hw=TPU_V5E_PROFILE, batch=8)
    assert plan.crossover in (None, 0, 1, 2, 5, 6, 9, 12)


@pytest.mark.parametrize("kw", [dict(), dict(crossover="auto")], ids=["spatial", "crossover"])
def test_the_dp_cuts_only_where_the_rule_allows(kw):
    groups = optimize_grouping((64, 64), V3_12, 2, 2, TPU_V5E_PROFILE, batch=8, **kw)
    for g in groups:
        if g.mode == "spatial":
            assert shortcut_group_error(V3_12, g.start, g.end) is None
    build_stack_plan((64, 64), V3_12, 2, 2, groups)


def test_the_cost_model_prices_the_add():
    one = [LayerDef(3, 1, 8, 8, batch_norm=True), LayerDef(3, 1, 8, 8, batch_norm=True)]
    two = one + [shortcut_layer(8, -2)]
    c1 = profile_cost((16, 16), one, no_grouping(2), 1, 1, TPU_V5E_PROFILE, batch=2)
    c2 = profile_cost((16, 16), two, no_grouping(3), 1, 1, TPU_V5E_PROFILE, batch=2)
    # one add a position and channel: 16*16*8 per sample, one pass
    assert c2["compute"] - c1["compute"] == pytest.approx(2 * 16 * 16 * 8 / TPU_V5E_PROFILE.flops)
    assert c2["weights"] == pytest.approx(c1["weights"])      # no filters


def test_peak_memory_charges_each_skip_by_hand():
    """Stage 1 at 64x64 on a 2x2 grid: layers 0..4, one unit (2, 3, 4)
    whose shortcut adds layer 1's 32x32x64 output."""
    layers, b = V3[:5], 2
    base = peak_device_memory((64, 64), layers, no_grouping(5), 2, 2, batch=b)
    assert base["skips"] == b * 16 * 16 * 64 * 4          # one core tile
    fused = [Group(0, 1), Group(2, 4)]
    mem = peak_device_memory((64, 64), layers, fused, 2, 2, batch=b)
    assert mem["skips"] == b * 16 * 16 * 64 * 4           # its output is a group end
    inner = [Group(0, 1), Group(2, 3), Group(4, 4)]
    assert peak_device_memory((64, 64), layers, inner, 2, 2, batch=b)["skips"] == base["skips"]
    data = peak_device_memory((64, 64), layers, [Group(0, 1), Group(2, 4, "data")], 2, 2, batch=8)
    assert data["skips"] == 2 * 32 * 32 * 64 * 4         # 8 samples over 4 devices, whole maps
    assert base["total"] == pytest.approx(
        base["activations"] + base["halo"] + base["filters"] + base["skips"])
    chain = peak_device_memory((64, 64), yolov2_16_layers()[:4], no_grouping(4), 2, 2, batch=b)
    assert chain["skips"] == 0


def test_skip_halo_inside_a_group_is_charged():
    """A shortcut inside a group whose later layers carry a halo keeps its
    skip at the shortcut's extended extent."""
    layers = V3[:8]                                     # 5..7: conv/2, 1x1, 3x3
    groups = [Group(0, 0), Group(1, 1), Group(2, 7)]    # 4 inside, 7 a 3x3 after it
    assert shortcut_group_error(layers, 2, 7) is None   # skip (layer 1) is the input
    mem = peak_device_memory((64, 64), layers, groups, 2, 2, batch=1)
    # after layer 4 the group still needs layer 5 (3x3/2: 1, 0) then 7 (3x3: 1, 1 at
    # stride 2): halo (3, 2) at the 16x16 core
    assert mem["skips"] == (16 + 3 + 2) ** 2 * 64 * 4


def test_the_launcher_trains_yolov3(tmp_path, capsys):
    from repro.launch.train import parse_args, run_tiled

    args = parse_args(["--arch", "yolov3-tiled", "--depth", "9", "--input-hw", "16",
                       "--batch", "2", "--grad-accum", "2", "--steps", "2",
                       "--optimizer", "sgd", "--ckpt-dir", str(tmp_path)])
    run = run_tiled(args)
    assert run.report.steps_done == 2 and run.report.restarts == 0
    assert len(run.arch.plan.layers) == 9 and run.arch.plan.layers[8].shortcut == -3
    with pytest.raises(SystemExit, match="62 layers"):
        run_tiled(parse_args(["--arch", "yolov3-tiled", "--depth", "63"]))


def _bad_stack(kind):
    conv = lambda cin, cout, s=1: LayerDef(3, s, cin, cout, batch_norm=True, use_bias=False)
    if kind == "later":
        return [conv(3, 8), LayerDef(1, 1, 8, 8, pad=0, act="linear", use_bias=False, shortcut=1), conv(8, 8)]
    if kind == "channels":
        return [conv(3, 8), conv(8, 16), shortcut_layer(16, -2)]
    return [conv(3, 8), conv(8, 8, 2), shortcut_layer(8, -2)]          # a stride between


@pytest.mark.parametrize("kind,match", [
    ("later", r"shortcut layer 1 \(from=1\) reads layer 2"),
    ("channels", r"shortcut layer 2 \(from=-2\) adds 8 channels"),
    ("stride", r"shortcut layer 2 \(from=-2\) adds layer 0's \(16, 16\) map"),
])
def test_a_shortcut_whose_addends_differ_is_refused(kind, match):
    with pytest.raises(ValueError, match=match):
        build_stack_plan((16, 16), _bad_stack(kind), 1, 1)
