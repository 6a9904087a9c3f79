"""Layer: sparse push. Share of the traced operation time spent in merging the
batch's gradients per row (``pt.push.accumulate``: in the dense push the
zeroed [C+1, 4+dim] accumulator, the scatter-add and its column slices)
(``harness/scopes.py``); None for a program without the scopes."""

from harness import scopes


def read(ctx):
    return scopes.share(ctx, "pt.push.accumulate")
