"""Layer: pass build / residency. Seconds of the host's key work:
``pt.pass.dedup`` + ``pt.pass.index`` (FeasignIndex insert, shard spread) +
``pt.pass.map_build`` (cuckoo build), from the program's own spans
(``core/profiler.host_spans``) under the ``pt.pass.begin`` root of the
cell's pass (``harness/scopes.py``)."""

from harness import scopes


def read(ctx):
    return scopes.phase_seconds("begin", "dedup", "index", "map_build")
