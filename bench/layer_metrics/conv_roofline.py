"""The conv passes' share of their roofline: the least time a chip needs for
its share of a step's conv passes (``work.conv_least_seconds``, each pass
bounded by FLOPs over peak or bytes over HBM bandwidth) times the window's
steps, over the device time of the operations that carry the conv MACs.

The trace names each op by its HLO instruction and gives no category, so the
ops that carry the MACs are read from the compiled step that ran
(``devtrace.conv_ops``): XLA's convolutions and the fusions whose computation
holds one (xla backend), and the Mosaic kernels' custom calls, target
``tpu_custom_call`` (pallas backend). The Pallas wrapper's column-fold copies
are outside the kernels and are not counted. A fusion that holds a conv may
also hold batch-norm or leaky work; that time counts, so the share is a lower
bound on the convs' own."""

import devtrace
import work


def read(run):
    t = run.trace
    conv_s = t.seconds_of(devtrace.conv_ops(run.result["hlo"]))
    if conv_s <= 0:
        return None
    cell = run.ctx.cell
    least = work.conv_least_seconds(run.ctx.cfg, cell["batch"], len(run.ctx.devices), run.peak)
    return 100.0 * least * run.result["window"]["steps"] / conv_s
