"""Share of the traced window in which the chip is idle while the host is
inside the driver's ``driver.wait`` span (``block_until_ready`` on the step's
loss): the chip waits on work the host has already issued, such as the input
copy of ``arch.place_batch``. Averaged over chips; with ``idle_host_share`` it
sums to ``device_idle_share`` (``bench/scopes.py``)."""

import scopes


def read(run):
    split = scopes.run_idle_split(run)
    return None if split is None else 100.0 * split["wait"] / run.trace.window_s
