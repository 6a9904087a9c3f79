"""Layer: expert layer. Share of the traced operation time spent routing
(``pt.moe.route``: the float32 router matmul, softmax, top-k, the two
router losses, the counts) (``harness/scopes.py``); None for a program
without the scopes."""

from harness import scopes


def read(ctx):
    return scopes.share(ctx, "pt.moe.route")
