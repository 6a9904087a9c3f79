"""Layer: entry points / host dispatch. Median host time inside one
dispatch call (the enqueue, not the device work), window dispatches only.
Moves the cell's rate only once ``device_idle_share`` is not ~0."""

import statistics


def read(ctx):
    d = ctx["window"]["dispatch_s"]
    return statistics.median(d) * 1e3 if d else None
