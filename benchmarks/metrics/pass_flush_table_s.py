"""Layer: pass build / residency. Seconds of the host index and table walks:
``pt.pass.flush_index`` + ``pt.pass.flush_export`` + ``pt.pass.import``,
from the program's own spans (``core/profiler.host_spans``) under the
``pt.pass.end`` root of the cell's pass (``harness/scopes.py``)."""

from harness import scopes


def read(ctx):
    return scopes.phase_seconds("end", "flush_index", "flush_export", "import")
