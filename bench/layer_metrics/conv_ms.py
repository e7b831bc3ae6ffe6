"""Device ms per window step of the compiled instructions under the program's
``conv`` named scope: the conv backend call: XLA's convolutions and their fusions, or the Pallas
kernels, with their data and weight gradients. Averaged over chips
(``bench/scopes.py``)."""

import scopes


def read(run):
    return scopes.scope_ms(run, "conv")
