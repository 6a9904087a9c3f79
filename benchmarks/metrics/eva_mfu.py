"""Layer: dense model step. FLOPs the forward and backward passes of the
EVA byte decoder require per token (``harness/flops_eva.
train_flops_per_token``: projections, the products the EVA mask leaves, the
dense SwiGLU of every block, the eight heads; nothing recomputed or padded
counted) times the token rate of this run, over the chip's published bf16
peak: the share of the whole step that bounds any later claim in this
cell."""


def read(ctx):
    cfg = ctx["cell"].config
    if (ctx["system"].unit != "tokens" or ctx["rehearse"]
            or cfg.get("attention_class") != "eva"):
        return None
    from harness import device, flops_eva

    per_token = flops_eva.train_flops_per_token(cfg, ctx["system"].seq)
    peak = device.peaks(ctx["device_kind"])["bf16_flops"]
    return per_token * ctx["rate_per_chip"] / peak
