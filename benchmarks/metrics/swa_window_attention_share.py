"""Layer: dense model step. Share of the traced operation time spent in the
WINDOWED attention blocks (``pt.attn.window`` anywhere in the operation's
name: the norm, q, k, v, rotary, the repeat, the three kernels under the
4096-key window, the output projection and the residual of three layers
in four) (``harness/scope_paths.py``). None for a program without the
scope."""

from harness import scope_paths


def read(ctx):
    return scope_paths.share_under(ctx, "pt.attn.window")
