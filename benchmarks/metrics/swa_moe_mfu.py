"""Layer: dense model step. FLOPs the forward and backward passes of the
sliding-window expert decoder require per token as this chip runs it
(``harness/flops_swa.train_flops_per_token``: each layer's attention over
the pairs ITS mask leaves, the routers, the held share of the experts, the
untied head over the held vocabulary; nothing recomputed or padded
counted) times the token rate of this run, over the chip's published bf16
peak: the share of the whole step that bounds any later claim in this
cell."""


def read(ctx):
    cfg = ctx["cell"].config
    if (ctx["system"].unit != "tokens" or ctx["rehearse"]
            or "sliding_window_layout" not in cfg):
        return None
    from harness import device, flops_swa

    per_token = flops_swa.train_flops_per_token(cfg, ctx["system"].seq)
    peak = device.peaks(ctx["device_kind"])["bf16_flops"]
    return per_token * ctx["rate_per_chip"] / peak
