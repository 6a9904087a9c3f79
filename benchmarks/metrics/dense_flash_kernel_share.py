"""Layer: dense model step. Share of the traced operation time spent in the
three Mosaic flash-attention kernels alone (``pt.flash_fwd``,
``pt.flash_bwd_dq``, ``pt.flash_bwd_dkv``) (``harness/scopes.py``); None for
a program without the scopes."""

from harness import scopes


def read(ctx):
    return scopes.share(ctx, prefix="pt.flash_")
