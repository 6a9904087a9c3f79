"""Layer: expert layer. Assignments that reached no expert, summed over
the window's dispatches — from the step's own ``tokens_dropped`` counter
(the program's buffers). Dropless: must be 0, and a dispatch with another
value counts as failed. None for a system that reports no such counter."""


def read(ctx):
    return getattr(ctx["system"], "tokens_dropped", None)
