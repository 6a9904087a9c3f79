"""Layer: residual path. The largest ``|row sum - 1|`` or ``|column sum -
1|`` of ``H_res`` over every token and sublayer of every dispatch of the
window — from the step's own ``hc_res_err`` counter (a buffer of the
program). Of the order of ``hc_eps`` = 1e-6: the constraint holding; a
dispatch above 1e-4 counts as failed. None for a system that reports no
such counter."""


def read(ctx):
    return getattr(ctx["system"], "hc_res_err", None)
