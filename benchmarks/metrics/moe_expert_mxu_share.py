"""Layer: expert layer. The grouped matmuls' share of their roofline: the
FLOPs the expert layers REQUIRE a step (``harness/flops_moe.py``: three
passes x three matrices x 2 * hidden * width for each of the tokens'
assignments) over the device time a step spends under ``pt.moe.experts``
(its share of the traced operation time x that time / the window's
dispatches: the gate and the casts are inside, so this is a floor on the
matmuls' own share), over the chip's published bf16 peak. Compute-bound:
the banks are read once a pass. None without a trace or the scopes."""

from harness import scopes


def read(ctx):
    red = ctx.get("trace")
    share = scopes.share(ctx, "pt.moe.experts")
    if not red or not share or ctx["rehearse"]:
        return None
    from harness import device, flops_moe

    cfg = ctx["cell"].config
    system = ctx["system"]
    step_s = share * sum(red["op_self_s"].values()) / ctx["window"]["dispatches"]
    required = (flops_moe.expert_matmul_flops_per_token(cfg)
                * cfg["num_hidden_layers"] * system.units_per_dispatch
                / ctx["chips"])
    return required / step_s / device.peaks(ctx["device_kind"])["bf16_flops"]
