"""Layer: kernels. flash_fwd's share of its roofline, in percent, over the
step's four calls of two kinds (one global causal, three under the
4096-key window; 28 query / 4 key-value heads of 128): the sum over the
calls of the larger of required FLOPs / the published bf16 peak and
required bytes / the published HBM bandwidth
(harness/flops_swa.flash_kernel_floor: the pairs each mask LEAVES —
L(L+1)/2, or W(W+1)/2 + (L-W)W — k and v at their 4 heads, not the 28 they
are repeated to) over the kernel's measured device time a step
(harness/kernels_swa.py). It cannot pass 100 whatever implements the
window. None without a trace or the kernel in it."""

from harness import kernels_swa


def read(ctx):
    return kernels_swa.roofline_percent(ctx, "flash_fwd")
