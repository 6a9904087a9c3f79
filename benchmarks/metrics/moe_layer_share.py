"""Layer: expert layer. Share of the traced operation time spent in the
expert sublayers as a whole: ``pt.ffn`` (here the norm and the residual
round the experts) and every ``pt.moe.*`` scope inside it (route,
dispatch, experts, combine) (``harness/scopes.py``); None for a program
without the scopes."""

from harness import scopes


def read(ctx):
    return scopes.share(ctx, "pt.ffn", prefix="pt.moe.")
