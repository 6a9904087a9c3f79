"""Layer: dense model step. Share of the traced operation time spent in the
EVA mixer whole (``pt.attn`` anywhere in the operation's name: the norm,
``pt.eva.qkv``, ``pt.rope``, ``pt.eva.prep``, the three kernels, the output
projection and the residual of every block) (``harness/scope_paths.py``).
None for a program without the scope."""

from harness import scope_paths


def read(ctx):
    return scope_paths.share_under(ctx, "pt.attn")
