"""Share of the traced window in which a chip ran a collective operation and
nothing else: the union of its collective ops' intervals less the part that
other ops overlap, over the window, averaged over chips. Collective ops are
those ``devtrace.is_collective`` names (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all, send and receive)."""


def read(run):
    t = run.trace
    if t.collective_s <= 0:
        return None
    return 100.0 * t.collective_exposed_s / t.window_s
