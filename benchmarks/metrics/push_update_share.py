"""Layer: sparse push. Share of the traced operation time spent in the per-row
rule and the touched-mask select over the table (``pt.push.update``)
(``harness/scopes.py``); None for a program without the scopes."""

from harness import scopes


def read(ctx):
    return scopes.share(ctx, "pt.push.update")
