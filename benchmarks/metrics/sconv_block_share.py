"""Layer: dense model step. Share of the traced operation time spent in the
gated short-convolution blocks' mixer sublayers: ``pt.conv`` (the norm and
the residual) and ``pt.conv.in`` / ``.mix`` / ``.out`` inside it
(``harness/scopes.py``); None for a program without the scopes."""

from harness import scopes


def read(ctx):
    return scopes.share(ctx, "pt.conv", prefix="pt.conv.") or None
