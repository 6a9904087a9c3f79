"""Layer: kernels. The gated short convolution's share of the chip's peak,
over the WHOLE operator: the FLOPs its two projections REQUIRE a step
(``harness/flops_lfm2.conv_operator_flops``: W_in and W_out, three
passes, every conv block) over the device time a step spends under
``pt.conv.in``, ``pt.conv.mix`` and ``pt.conv.out`` together (their share
of the traced operation time x that time / the window's dispatches), over
the published bf16 peak. Taken over the operator and not over the mix, so
that a fusion of the gates into a projection cannot push a reading past
1. None without a trace or the scopes."""

from harness import scopes


def read(ctx):
    red = ctx.get("trace")
    share = scopes.share(ctx, prefix="pt.conv.")
    if not red or not share or ctx["rehearse"]:
        return None
    from harness import device, flops_lfm2

    step_s = share * sum(red["op_self_s"].values()) / ctx["window"]["dispatches"]
    required = flops_lfm2.conv_operator_flops(
        ctx["cell"].config, ctx["system"].units_per_dispatch / ctx["chips"])
    return required / step_s / device.peaks(ctx["device_kind"])["bf16_flops"]
