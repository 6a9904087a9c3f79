"""Layer: dense model step. Share of the traced operation time spent under
``pt.conv.mix`` alone — both gates and the three taps, the part of the
operator that is bound by memory bandwidth (``harness/scopes.py``). None
for a program without the scope, and where XLA fused every one of its
operations into a neighbour's (a share of exactly 0 is not a reading)."""

from harness import scopes


def read(ctx):
    return scopes.share(ctx, "pt.conv.mix") or None
