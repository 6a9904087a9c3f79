"""Layer: expert layer. Share of the traced operation time spent in the
experts themselves (``pt.moe.experts``: the three grouped matmuls, their
gradients, the gate and the casts of the banks) (``harness/scopes.py``);
None for a program without the scopes."""

from harness import scopes


def read(ctx):
    return scopes.share(ctx, "pt.moe.experts")
