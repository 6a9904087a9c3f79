"""Layer: pass build / residency. Seconds of ``pt.pass.upload``: key map and
column arrays to the device(s), closed by ``block_until_ready`` on what was
uploaded, from the program's own spans (``core/profiler.host_spans``) under
the ``pt.pass.begin`` root of the cell's pass (``harness/scopes.py``)."""

from harness import scopes


def read(ctx):
    return scopes.phase_seconds("begin", "upload")
