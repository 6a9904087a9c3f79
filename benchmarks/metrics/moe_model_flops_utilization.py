"""Layer: dense model step. FLOPs the forward and backward passes of a
sparse-expert causal decoder require per token
(``harness/flops_moe.py``: only the experts a token is routed to, causal
attention, the untied head; nothing recomputed counted) times the token
rate of this run, over the chip's published bf16 peak."""


def read(ctx):
    if ctx["system"].unit != "tokens" or ctx["rehearse"]:
        return None
    from harness import device, flops_moe

    per_token = flops_moe.causal_moe_train_flops_per_token(
        ctx["cell"].config, ctx["system"].seq)
    peak = device.peaks(ctx["device_kind"])["bf16_flops"]
    return per_token * ctx["rate_per_chip"] / peak
