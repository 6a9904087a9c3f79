"""Layer: expert layer. Share of the traced operation time spent moving
rows: ``pt.moe.dispatch`` (the sort by expert and the gather into expert
order) and ``pt.moe.combine`` (the gather back and the weighted sum over a
token's experts), forward and backward (``harness/scopes.py``); None for a
program without the scopes."""

from harness import scopes


def read(ctx):
    return scopes.share(ctx, "pt.moe.dispatch", "pt.moe.combine")
