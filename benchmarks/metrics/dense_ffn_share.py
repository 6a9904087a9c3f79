"""Layer: dense model step. Share of the traced operation time spent in the
feed-forward sublayers (``pt.ffn``: pre-LN, both matmuls, GELU, residual)
(``harness/scopes.py``); None for a program without the scopes."""

from harness import scopes


def read(ctx):
    return scopes.share(ctx, "pt.ffn")
