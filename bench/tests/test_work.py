"""The conv work counted from shapes, against a count by hand."""
import json

import pytest

import work
from run_cell import BENCH

# 2*K*K*Cin*Cout*H*W per image at 416x416, layer by layer (maps halve at each pool)
HAND_416 = [
    2 * 9 * 3 * 32 * 416 * 416,
    2 * 9 * 32 * 64 * 208 * 208,
    2 * 9 * 64 * 128 * 104 * 104, 2 * 1 * 128 * 64 * 104 * 104, 2 * 9 * 64 * 128 * 104 * 104,
    2 * 9 * 128 * 256 * 52 * 52, 2 * 1 * 256 * 128 * 52 * 52, 2 * 9 * 128 * 256 * 52 * 52,
    2 * 9 * 256 * 512 * 26 * 26, 2 * 1 * 512 * 256 * 26 * 26, 2 * 9 * 256 * 512 * 26 * 26,
    2 * 1 * 512 * 256 * 26 * 26,
]


def cfg(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_forward_flops_416():
    assert sum(HAND_416) == 12_172_066_816
    assert work.forward_flops_per_image(cfg("yolov2-16.voc416")) == sum(HAND_416)


def test_train_flops_416_leave_out_first_dgrad():
    assert work.train_flops_per_image(cfg("yolov2-16.voc416")) == 3 * sum(HAND_416) - HAND_416[0]


def test_train_flops_608_scale_with_the_map():
    f416 = work.train_flops_per_image(cfg("yolov2-16.voc416"))
    f608 = work.train_flops_per_image(cfg("yolov2-16.coco608"))
    assert f608 == pytest.approx(f416 * (608 / 416) ** 2, rel=1e-12)
    assert round(f608 / 1e9, 1) == 77.4


def test_least_seconds_bounded_by_flops_and_bytes():
    c = cfg("yolov2-16.voc416")
    peak = work.peaks_for("TPU v5 lite")
    least = work.conv_least_seconds(c, 64, 1, peak)
    flops = 64 * work.train_flops_per_image(c) / peak["bf16_flops_per_s"]
    nbytes = sum(64 * p.map_bytes + p.weight_bytes for p in work.conv_passes(c))
    assert max(flops, nbytes / peak["hbm_bytes_per_s"]) <= least <= flops + nbytes / peak["hbm_bytes_per_s"]
    # four chips split the maps, each keeps every filter
    assert work.conv_least_seconds(c, 64, 4, peak) < least


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        work.peaks_for("cpu")
