"""Layer: dense model step. Share of the traced operation time spent in the
attention sublayers, kernels included (``pt.attn`` and every ``pt.flash_*``
inside it: pre-LN, QKV, flash kernels, projection, residual)
(``harness/scopes.py``); None for a program without the scopes."""

from harness import scopes


def read(ctx):
    return scopes.share(ctx, "pt.attn", prefix="pt.flash_")
