"""Layer: entry points / compile. Seconds spent reading executables from
the persistent compile cache and loading them onto the device before the
window's ``t0``: the program's ``pt.compile`` spans with ``hit`` 1
(``harness/setup_spans.py``). 0.0 where the cache is off or empty. None
for a program without ``pt.compile*`` spans."""

from harness import setup_spans


def read(ctx):
    return setup_spans.seconds(ctx, setup_spans.COMPILE, hit=1)
