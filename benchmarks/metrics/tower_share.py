"""Layer: dense tower. Share of the traced operation time spent in the model's
forward and backward (``pt.tower``: FM, DNN, loss, and their transposes)
(``harness/scopes.py``); None for a program without the scopes."""

from harness import scopes


def read(ctx):
    return scopes.share(ctx, "pt.tower")
