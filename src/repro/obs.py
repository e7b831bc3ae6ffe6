"""Names of the program's profiler spans and named scopes (DESIGN.md §15).

Host spans are ``jax.profiler.TraceAnnotation``s in the training driver and
the batch placement; the driver wraps each loop attempt in a
``jax.profiler.StepTraceAnnotation(STEP, step_num=...)``. Scopes are
``jax.named_scope``s in the traced step: they land in each compiled
instruction's ``metadata={op_name=...}``, and backward ops inherit them as
``transpose(jvp(layer01))/pool/...``. Spans are recorded only while a
``jax.profiler`` trace runs, and scopes only name compiled instructions:
nothing here records anything itself.
"""
from __future__ import annotations

import jax

# host spans: runtime.driver.run_training and TiledCNNArch.place_batch
STEP = "train"
MAKE_BATCH = "driver.make_batch"
DISPATCH = "driver.dispatch"
WAIT = "driver.wait"
METRICS = "driver.metrics"
CHECKPOINT = "driver.checkpoint"
RESTORE = "driver.restore"
REPLAN = "driver.replan"
PLACE_BATCH = "arch.place_batch"

# named scopes of the traced train step, inside a layer's scope or beside it
CONV, BN, POOL, HALO, RESHARD = "conv", "bn", "pool", "halo", "reshard"
SHORTCUT = "shortcut"
LOSS, GRAD_SUM, OPTIMIZER = "loss", "grad_sum", "optimizer"
SCOPES = (CONV, BN, POOL, HALO, RESHARD, LOSS, GRAD_SUM, OPTIMIZER, SHORTCUT)


def layer_scope(i: int):
    """The named scope of the plan's layer ``i``, numbered from 1: ``layer01``
    is the plan's layer 0."""
    return jax.named_scope(f"layer{i + 1:02d}")
