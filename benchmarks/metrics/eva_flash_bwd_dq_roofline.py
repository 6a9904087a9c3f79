"""Layer: kernels. flash_bwd_dq's share of its roofline, in percent, under
the EVA mask (32 heads of 128 over [summaries | keys], 8192 positions in
aligned 2048-key windows, 16-key chunks): a layer's one required call —
the larger of required FLOPs / the published bf16 peak and required bytes /
the published HBM bandwidth (harness/flops_eva.flash_kernel_floor: the
9,965,568 products a head the MASK leaves, not the block pairs walked; the
384 summaries some query reads) — times the layers, over the kernel's
measured device time a step (harness/kernels_eva.py; recomputed calls are
time, not work). It cannot pass 100 whatever implements the mask. None
without a trace or the kernel in it."""

from harness import kernels_eva


def read(ctx):
    return kernels_eva.roofline_percent(ctx, "flash_bwd_dq")
