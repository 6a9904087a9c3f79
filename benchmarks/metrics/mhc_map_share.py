"""Layer: residual path. Share of the traced operation time spent making
the mappings alone (``pt.hc.map``: the norm over the four streams, the
[tokens, 14336] x [14336, 24] projection at precision highest, the
sigmoids, the twenty Sinkhorn steps and their backward)
(``harness/scopes.py``). None for a program without the scope."""

from harness import scopes


def read(ctx):
    got = scopes.scope_shares(ctx)
    return None if got is None else got.get("pt.hc.map")
