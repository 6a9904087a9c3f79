"""Layer: collectives. Share of the traced operation time spent in the local
work of key routing (``pt.route``: dedup sort, owner, bucketing, un-
bucketing; the all-to-alls themselves carry no scope and stay
``collective_share``) (``harness/scopes.py``); None for a program without
the scopes."""

from harness import scopes


def read(ctx):
    return scopes.share(ctx, "pt.route")
