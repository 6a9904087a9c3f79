"""Tokens whose dispatch completed in the window, over the time those
dispatches took, over the chips (``harness/window.py``)."""


def read(ctx):
    return ctx["rate_per_chip"] if ctx["system"].unit == "tokens" else None
