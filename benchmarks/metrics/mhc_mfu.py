"""Layer: dense model step. FLOPs the forward and backward passes of the
hyper-connected latent-attention expert decoder require per token as this
chip runs it (``harness/flops_mhc.train_flops_per_token``: causal attention
at 192 / 128, the residual path's own matmuls, the shared expert and the
held experts' part AS THE WINDOW ROUTED — its mean ``held_assignments`` a
token —, the head over the held vocabulary; nothing recomputed or padded
counted) times the token rate of this run, over the chip's published bf16
peak: the share of the whole step."""


def read(ctx):
    cfg, system = ctx["cell"].config, ctx["system"]
    held = getattr(system, "held_assignments_per_dispatch", None)
    if (system.unit != "tokens" or ctx["rehearse"] or held is None
            or "hc_mult" not in cfg):
        return None
    from harness import device, flops_mhc

    experts = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    per_token = flops_mhc.train_flops_per_token(
        cfg, system.seq, held / system.units_per_dispatch / experts)
    peak = device.peaks(ctx["device_kind"])["bf16_flops"]
    return per_token * ctx["rate_per_chip"] / peak
