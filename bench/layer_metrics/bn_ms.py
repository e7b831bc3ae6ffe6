"""Device ms per window step of the compiled instructions under the program's
``bn`` named scope: ``_finish_layer``: batch norm with its cross-tile psums, the unfused
activation and the off-map mask, forward and backward. Averaged over chips
(``bench/scopes.py``)."""

import scopes


def read(run):
    return scopes.scope_ms(run, "bn")
