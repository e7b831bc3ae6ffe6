"""Work of a training step computed from shapes: conv FLOPs and the least
bytes each conv must move, and the peaks of the chip it runs on.

The count is the work the algorithm requires, whatever implements it: for each
conv layer a forward, a data gradient (none for the first layer, whose input
needs no gradient) and a weight gradient, each ``2 * K*K * Cin * Cout * OH*OW``
per image. Recomputed halo borders and any other redundant work do not count.
A pass's least bytes are its two operands read and its result written once, in
the arguments' dtype.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

from reference import layers_from_config

PEAKS = Path(__file__).resolve().parent / "peaks.json"


@dataclasses.dataclass(frozen=True)
class ConvPass:
    layer: int          # index in the configuration's layer list
    kind: str           # "fwd" | "dgrad" | "wgrad"
    flops: float        # per image
    map_bytes: float    # per image: activation-sized operands and result
    weight_bytes: float


def conv_passes(cfg: dict, dtype_bytes: int = 4) -> list[ConvPass]:
    h, w = cfg["height"], cfg["width"]
    out, first = [], True
    for i, l in enumerate(layers_from_config(cfg)):
        oh, ow = h // l.stride, w // l.stride
        if l.kind == "conv":
            flops = 2.0 * l.size * l.size * l.cin * l.cout * oh * ow
            x, y = h * w * l.cin * dtype_bytes, oh * ow * l.cout * dtype_bytes
            wb = l.size * l.size * l.cin * l.cout * dtype_bytes
            out.append(ConvPass(i, "fwd", flops, x + y, wb))        # x, w -> y
            if not first:
                out.append(ConvPass(i, "dgrad", flops, y + x, wb))  # dy, w -> dx
            out.append(ConvPass(i, "wgrad", flops, x + y, wb))      # x, dy -> dw
            first = False
        h, w = oh, ow
    return out


def train_flops_per_image(cfg: dict) -> float:
    return sum(p.flops for p in conv_passes(cfg))


def forward_flops_per_image(cfg: dict) -> float:
    return sum(p.flops for p in conv_passes(cfg) if p.kind == "fwd")


def conv_least_seconds(cfg: dict, batch: int, chips: int, peak: dict, dtype_bytes: int = 4) -> float:
    """Least time one chip needs for its share of a step's conv passes: each
    pass bounded by the larger of its FLOPs over the peak and its bytes over
    the HBM bandwidth. The maps split over the chips; each holds every filter."""
    total = 0.0
    for p in conv_passes(cfg, dtype_bytes):
        flops = p.flops * batch / chips
        nbytes = p.map_bytes * batch / chips + p.weight_bytes
        total += max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
    return total


def peaks_for(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS.name}")
    return table[device_kind]
