"""Layer: residual path. Share of the traced operation time spent on the
hyper-connected residual path: every ``pt.hc.*`` scope (``pt.hc.map``: the
streams' norm, the mappings' projection, the sigmoids and the Sinkhorn
steps; ``pt.hc.collect``: u = H_pre X; ``pt.hc.scatter``: X' = H_res X +
H_post y), forward, recomputed and backward, in every sublayer
(``harness/scopes.py``); beside 1.3% of the step's required FLOPs. None for
a program without the scopes."""

from harness import scopes


def read(ctx):
    got = scopes.scope_shares(ctx)
    if got is None or not any(k.startswith("pt.hc.") for k in got):
        return None
    return scopes.share(ctx, prefix="pt.hc.")
