"""Layer: pass build / residency. Seconds of ``pt.pass.fetch``: ``device_get``
of the working set, from the program's own spans
(``core/profiler.host_spans``) under the ``pt.pass.end`` root of the cell's
pass (``harness/scopes.py``)."""

from harness import scopes


def read(ctx):
    return scopes.phase_seconds("end", "fetch")
