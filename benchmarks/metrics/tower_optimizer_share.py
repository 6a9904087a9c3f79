"""Layer: dense tower. Share of the traced operation time spent in the dense
optimizer's update (``pt.dense_opt``) (``harness/scopes.py``); None for a
program without the scopes."""

from harness import scopes


def read(ctx):
    return scopes.share(ctx, "pt.dense_opt")
