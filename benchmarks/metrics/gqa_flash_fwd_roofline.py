"""Layer: kernels. flash_fwd's share of its roofline, in percent, at the
grouped-query widths (32 query / 8 key-value heads of 64, causal): the
larger of required FLOPs / the published bf16 peak and required bytes /
the published HBM bandwidth (harness/flops_lfm2.flash_kernel_floor: 64
wide, not the 128 lanes a head is padded to; k and v at their 8 heads,
not the 32 they are repeated to; the positions the causal mask leaves)
over the kernel's measured device time a call (harness/kernels_gqa.py).
None without a trace or the kernel in it."""

from harness import kernels_gqa


def read(ctx):
    return kernels_gqa.roofline_percent(ctx, "flash_fwd")
