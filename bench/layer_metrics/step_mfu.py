"""The whole step's share of the chips' bf16 peak: the conv FLOPs a training
step requires per image (``work.train_flops_per_image``: forward, data and
weight gradients, no first-layer data gradient, nothing recomputed) times the
traced window's images per second on the host clock, over chips times peak."""

import work


def read(run):
    w = run.result["window"]
    rate = w["images"] / w["seconds"]
    chips = len(run.ctx.devices)
    return 100.0 * work.train_flops_per_image(run.ctx.cfg) * rate / (chips * run.peak["bf16_flops_per_s"])
