"""Layer: kernels. flash_bwd_dq's share of its roofline at the latent-
attention widths (q.k 192, v 128, causal): the larger of required FLOPs /
the published bf16 peak and required bytes / the published HBM bandwidth
(harness/flops_mla.flash_kernel_floor: unpadded widths, the positions
the causal mask leaves, each operand once) over the kernel's measured
device time a call (harness/kernels.py: its operations in the trace
over dispatches x blocks). None without a trace or the kernel in it."""

from harness import kernels


def read(ctx):
    return kernels.mla_roofline_share(ctx, "flash_bwd_dq")
