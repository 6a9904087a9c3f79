"""Largest working set of HBM on the cell's fullest device at any dispatch
completion inside the window: live arrays plus the loaded programs'
reserved temporaries (``harness/device.working_set_bytes``). What a step
needs to run; steady from run to run. Set-up's transients show only in
``device.memory_peak_bytes``."""


def read(ctx):
    b = ctx["hbm_window_bytes"]
    return b / 2**30 if b else None
