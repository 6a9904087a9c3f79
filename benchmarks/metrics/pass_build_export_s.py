"""Layer: pass build / residency. Seconds of ``pt.pass.export``: one
``export_full(create=True)`` walk of the host table, from the program's own
spans (``core/profiler.host_spans``) under the ``pt.pass.begin`` root of the
cell's pass (``harness/scopes.py``)."""

from harness import scopes


def read(ctx):
    return scopes.phase_seconds("begin", "export")
