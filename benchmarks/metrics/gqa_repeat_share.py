"""Layer: dense model step. Share of the traced operation time spent under
``pt.gqa.repeat``: k and v copied from their 8 heads to the 32 the flash
kernels take (and the sum back in the backward pass) — what a kv-head
argument in the kernels (ROADMAP R8) would remove (``harness/scopes.py``).
None for a program without the scope or where nothing carries it."""

from harness import scopes


def read(ctx):
    return scopes.share(ctx, "pt.gqa.repeat") or None
