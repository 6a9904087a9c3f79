"""Layer: dense model step. FLOPs the forward and backward passes require
per token (``harness/flops.py``, from the configuration's shapes,
attention included, nothing recomputed counted) times the token rate of
this run, over the chip's published bf16 peak."""


def read(ctx):
    if ctx["system"].unit != "tokens" or ctx["rehearse"]:
        return None
    from harness import device, flops

    per_token = flops.encoder_train_flops_per_token(ctx["cell"].config,
                                                    ctx["system"].seq)
    peak = device.peaks(ctx["device_kind"])["bf16_flops"]
    return per_token * ctx["rate_per_chip"] / peak
