"""Device ms per window step of the compiled instructions under the program's
``halo`` named scope: the group-input halo exchanges: their pads and strips and the
collective-permutes, forward and the reversed exchange of the backward. Averaged over chips
(``bench/scopes.py``)."""

import scopes


def read(run):
    return scopes.scope_ms(run, "halo")
