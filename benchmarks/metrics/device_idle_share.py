"""Layer: device. 1 - (union of device operation intervals) / (traced
window), mean over devices: the same number the driver derives from
``device.busy_s`` and ``device.window_s``."""


def read(ctx):
    red = ctx["trace"]
    return red["idle_share"] if red else None
