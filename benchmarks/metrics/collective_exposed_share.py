"""Layer: collectives. The part of ``collective_share`` during which no
other operation runs on that device."""


def read(ctx):
    red = ctx["trace"]
    if not red or ctx["chips"] < 2:
        return None
    return red["collective_exposed_s"] / red["window_s"]
