"""Layer: entry points / compile. Seconds of Python tracing before the
window's ``t0``: the self time of the program's ``pt.compile.trace`` spans
(one an outermost traced function: the step, the router balance, the
initialisers), from ``core/profiler.host_spans`` (``harness/setup_spans.py``).
None for a program without ``pt.compile*`` spans."""

from harness import setup_spans


def read(ctx):
    return setup_spans.seconds(ctx, setup_spans.TRACE)
