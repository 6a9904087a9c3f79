"""Layer: dense model step. Share of the traced operation time spent in the
latent-attention projections outside the kernels: ``pt.mla.q`` (W_qa, its
norm, W_qb, q's assembly), ``pt.mla.kv`` (W_kva, its norm, W_kvb, the
key's assembly and broadcast) and ``pt.rope`` (``harness/scopes.py``);
None for a program without the scopes."""

from harness import scopes


def read(ctx):
    return scopes.share(ctx, "pt.mla.q", "pt.mla.kv", "pt.rope")
