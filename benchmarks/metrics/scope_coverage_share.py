"""Layer: device. Share of the traced operation time whose operation carries a
``pt.*`` scope: 1 - the unscoped rest. The guard of every other ``*_share``
of ``harness/scopes.py``: a step served from a compile cache that predates
the scopes reads None here, not 0. The costliest unscoped operations are
printed as an earlier line of the run."""

from harness import scopes


def read(ctx):
    shares = scopes.scope_shares(ctx)
    return None if shares is None else 1.0 - shares.get(scopes.UNSCOPED, 0.0)
