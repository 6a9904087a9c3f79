"""Layer: pass build / residency. Seconds of ``pt.pass.layout``: the host re-
layout of the exported rows into the seven column arrays, from the program's
own spans (``core/profiler.host_spans``) under the ``pt.pass.begin`` root of
the cell's pass (``harness/scopes.py``)."""

from harness import scopes


def read(ctx):
    return scopes.phase_seconds("begin", "layout")
