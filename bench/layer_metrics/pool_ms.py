"""Device ms per window step of the compiled instructions under the program's
``pool`` named scope: the max-pool windows and their backward (``select-and-scatter``). Averaged over chips
(``bench/scopes.py``)."""

import scopes


def read(run):
    return scopes.scope_ms(run, "pool")
