"""YOLOv3's Darknet-53 backbone through its first 62 darknet sections.

darknet ``cfg/yolov3-voc.cfg`` (Redmon & Farhadi, "YOLOv3: An Incremental
Improvement", arXiv:1804.02767), sections 0-61: a 3x3 conv, then four
stages, each a 3x3 stride-2 conv followed by residual units of a 1x1 conv
halving the channels, a 3x3 conv restoring them and ``[shortcut] from=-3
activation=linear``. Layer 61 is the 26x26x512 map at 416x416 that the
detector's second scale routes from (``route -1, 61``), the same cut at
cumulative stride 16 as YOLOv2-16's 26x26 output.
"""
from __future__ import annotations

from repro.core.spatial import LayerDef, shortcut_layer
from repro.models.tiled_cnn import TiledCNNArch
from repro.models.yolo import plan_tiled_arch

#: (channels after the stride-2 conv, residual units) of each stage
STAGES = ((64, 1), (128, 2), (256, 8), (512, 8))


def yolov3_62_layers(in_ch: int = 3, batch_norm: bool = True) -> list[LayerDef]:
    """The 62 layers: 43 convolutions (BN, leaky) and 19 shortcuts."""
    conv = lambda cin, cout, k, s=1: LayerDef(
        k, s, cin, cout, act="leaky", batch_norm=batch_norm, use_bias=not batch_norm
    )
    layers = [conv(in_ch, 32, 3)]
    ch = 32
    for cout, units in STAGES:
        layers.append(conv(ch, cout, 3, 2))
        for _ in range(units):
            layers += [conv(cout, cout // 2, 1), conv(cout // 2, cout, 3), shortcut_layer(cout)]
        ch = cout
    return layers


def make_yolov3_tiled_arch(
    input_hw: tuple[int, int] = (64, 64),
    depth: int = 62,
    n: int = 1,
    m: int = 1,
    groups=None,
    *,
    batch_norm: bool = True,
    **plan_kw,
) -> TiledCNNArch:
    """Planner -> arch bundle for the unified trainer: the first ``depth``
    layers of ``yolov3_62_layers`` tiled n x m through ``plan_tiled_arch``,
    whose keywords (``make_yolo_tiled_arch``'s) ``plan_kw`` holds. Plans the
    shortcuts cannot run on are refused at plan time (DESIGN.md §16): the
    overlap schedule, non-uniform partitions, pipeline tails, and group edges
    or a crossover inside a residual unit where the skip would need a halo."""
    return plan_tiled_arch(
        yolov3_62_layers(batch_norm=batch_norm)[:depth], input_hw, n, m, groups, **plan_kw
    )
