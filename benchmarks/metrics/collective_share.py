"""Layer: collectives. Device time of all-to-all / all-gather / all-reduce
/ reduce-scatter / collective-permute operations over the traced window
(mean over devices)."""


def read(ctx):
    red = ctx["trace"]
    if not red or ctx["chips"] < 2:
        return None
    return red["collective_s"] / red["window_s"]
