"""Layer: dense model step. Share of the traced operation time spent in the
GLOBAL attention blocks (``pt.attn.full`` anywhere in the operation's
name: full causal attention with no positional encoding, one layer in
four — the norm, q, k, v, the repeat, the three kernels over every earlier
key, the output projection and the residual)
(``harness/scope_paths.py``). None for a program without the scope."""

from harness import scope_paths


def read(ctx):
    return scope_paths.share_under(ctx, "pt.attn.full")
