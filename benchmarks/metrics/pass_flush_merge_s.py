"""Layer: pass build / residency. Seconds of ``pt.pass.merge``: folding the
device columns into the exported rows, from the program's own spans
(``core/profiler.host_spans``) under the ``pt.pass.end`` root of the cell's
pass (``harness/scopes.py``)."""

from harness import scopes


def read(ctx):
    return scopes.phase_seconds("end", "merge")
