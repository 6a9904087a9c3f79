"""Layer: entry points / compile. Programs XLA compiled, not read from
the persistent cache, before the window's ``t0``: the number of the
program's ``pt.compile`` spans with ``hit`` 0 (``harness/setup_spans.py``).
0 in a warm run; a program that became another key to the cache shows as
1. None for a program without ``pt.compile*`` spans."""

from harness import setup_spans


def read(ctx):
    return setup_spans.count(ctx, setup_spans.COMPILE, hit=0)
