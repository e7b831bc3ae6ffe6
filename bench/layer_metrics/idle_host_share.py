"""Share of the traced window in which the chip is idle while the host is
anywhere but the driver's ``driver.wait`` span: making or placing the batch,
dispatching the step, reading the metrics back, the driver's bookkeeping. The
host holds the chip back for this time. Averaged over chips; with
``idle_wait_share`` it sums to ``device_idle_share`` (``bench/scopes.py``)."""

import scopes


def read(run):
    split = scopes.run_idle_split(run)
    return None if split is None else 100.0 * split["host"] / run.trace.window_s
