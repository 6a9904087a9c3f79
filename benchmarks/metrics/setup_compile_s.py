"""Layer: entry points / compile. Seconds XLA spent compiling before the
window's ``t0``: the program's ``pt.compile`` spans with ``hit`` 0 (the
persistent cache did not hold the program; ``harness/setup_spans.py``).
0.0 in a warm run. None for a program without ``pt.compile*`` spans."""

from harness import setup_spans


def read(ctx):
    return setup_spans.seconds(ctx, setup_spans.COMPILE, hit=0)
