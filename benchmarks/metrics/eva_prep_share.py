"""Layer: dense model step. Share of the traced operation time spent
making the chunk summaries (``pt.eva.prep``: the pooling softmax over each
16-key chunk, the pooled keys and values, forward and backward, in every
layer and its recomputation) (``harness/scope_paths.py``); None for a program
without the scope."""

from harness import scope_paths


def read(ctx):
    return scope_paths.share_under(ctx, "pt.eva.prep")
