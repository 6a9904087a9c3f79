"""Layer: dense model step. Share of the traced operation time spent in the
prediction module's own projection and norms (``pt.mtp``: the two input
norms, W_eh, its final norm; its block's time is under the block's own
scopes, its head under ``pt.head_loss``) (``harness/scopes.py``); None for
a program without the scopes."""

from harness import scopes


def read(ctx):
    return scopes.share(ctx, "pt.mtp")
