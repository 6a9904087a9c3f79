"""Layer: expert layer. Share of the traced operation time spent in the
shared expert's SwiGLU and its sum with the routed part
(``pt.moe.shared``) (``harness/scopes.py``); None for a program without
the scopes."""

from harness import scopes


def read(ctx):
    return scopes.share(ctx, "pt.moe.shared")
