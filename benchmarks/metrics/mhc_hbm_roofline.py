"""Layer: residual path. The residual path's share of its memory roofline,
in percent: the bytes a step's sublayers REQUIRE
(``harness/bytes_mhc.train_bytes_per_token``: the four streams and the
sublayer's input and result once a pass, forward and backward; nothing an
implementation reads twice, nothing recomputed) over the published HBM
bandwidth, over the device time a step spends under ``pt.hc.collect`` and
``pt.hc.scatter`` (their share of the traced operation time x that time /
the window's dispatches; the recomputed forward is inside, and counts as
time, not as bytes). It cannot pass 100 whatever implements the path. None
without a trace or the scopes, and on a rehearsal."""

from harness import scopes


def read(ctx):
    red, cfg = ctx.get("trace"), ctx["cell"].config
    got = scopes.scope_shares(ctx)
    if (not red or got is None or ctx["rehearse"] or "hc_mult" not in cfg
            or not {"pt.hc.collect", "pt.hc.scatter"} & set(got)):
        return None
    from harness import bytes_mhc, device

    share = scopes.share(ctx, "pt.hc.collect", "pt.hc.scatter")
    step_s = share * sum(red["op_self_s"].values()) \
        / ctx["window"]["dispatches"]
    system = ctx["system"]
    floor_s = bytes_mhc.train_bytes_per_token(cfg) \
        * system.units_per_dispatch / ctx["chips"] \
        / device.peaks(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * floor_s / step_s if step_s else None
