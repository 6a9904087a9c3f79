"""Layer: dense model step. FLOPs the forward and backward passes of the
latent-attention expert decoder require per token as this chip runs it
(``harness/flops_mla.train_flops_per_token``: the held share of the
experts, causal attention at 192 / 128, both heads over the held
vocabulary; nothing recomputed or padded counted) times the token rate of
this run, over the chip's published bf16 peak."""


def read(ctx):
    cfg = ctx["cell"].config
    if (ctx["system"].unit != "tokens" or ctx["rehearse"]
            or "qk_rope_head_dim" not in cfg):
        return None
    from harness import device, flops_mla

    per_token = flops_mla.train_flops_per_token(cfg, ctx["system"].seq)
    peak = device.peaks(ctx["device_kind"])["bf16_flops"]
    return per_token * ctx["rate_per_chip"] / peak
