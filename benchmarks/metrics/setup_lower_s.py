"""Layer: entry points / compile. Seconds of lowering to MLIR before the
window's ``t0``: the self time of the program's ``pt.compile.lower`` spans
(``harness/setup_spans.py``). None for a program without ``pt.compile*``
spans."""

from harness import setup_spans


def read(ctx):
    return setup_spans.seconds(ctx, setup_spans.LOWER)
