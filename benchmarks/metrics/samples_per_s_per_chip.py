"""Samples whose dispatch completed in the window, over the time those
dispatches took, over the chips (``harness/window.py``). Host clock closed
by ``block_until_ready``."""


def read(ctx):
    return ctx["rate_per_chip"] if ctx["system"].unit == "samples" else None
