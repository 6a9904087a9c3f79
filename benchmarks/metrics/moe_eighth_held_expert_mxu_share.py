"""Layer: expert layer. The held experts' grouped matmuls' share of the
chip's peak where a layer holds an eighth of its experts: the FLOPs the
assignments that landed here REQUIRE a step
(``harness/flops_swa.held_expert_flops`` of the window's mean
``held_assignments``: three passes x three matrices of 2560 x 768) over
the device time a step spends under ``pt.moe.experts`` (its share of the
traced operation time x that time / the window's dispatches; the
backward's second forward of the form that ran is inside, and counts as
time, not as FLOPs), over the published bf16 peak. None without a trace,
the scopes or the counter, and for another configuration."""

from harness import scopes


def read(ctx):
    red = ctx.get("trace")
    share = scopes.share(ctx, "pt.moe.experts")
    held = getattr(ctx["system"], "held_assignments_per_dispatch", None)
    if not red or not share or not held or ctx["rehearse"] \
            or "sliding_window_layout" not in ctx["cell"].config:
        return None
    from harness import device, flops_swa

    step_s = share * sum(red["op_self_s"].values()) / ctx["window"]["dispatches"]
    required = flops_swa.held_expert_flops(ctx["cell"].config,
                                           held / ctx["chips"])
    return required / step_s / device.peaks(ctx["device_kind"])["bf16_flops"]
