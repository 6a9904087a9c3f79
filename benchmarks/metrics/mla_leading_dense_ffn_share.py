"""Layer: dense model step. Share of the traced operation time spent in
the leading dense layer's feed-forward sublayer (``pt.ffn.dense``: its
norm, the 7168-wide SwiGLU and the residual), which ``moe_layer_share``
(``pt.ffn`` + ``pt.moe.*``) therefore leaves out (``harness/scopes.py``);
None for a program without the scope."""

from harness import scopes


def read(ctx):
    return scopes.share(ctx, "pt.ffn.dense")
