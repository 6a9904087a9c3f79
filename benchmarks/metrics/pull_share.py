"""Layer: sparse pull. Share of the traced operation time spent in the
embedding gather (``pt.pull``; on the mesh the owner-side gather between the
two all-to-alls) (``harness/scopes.py``); None for a program without the
scopes."""

from harness import scopes


def read(ctx):
    return scopes.share(ctx, "pt.pull")
