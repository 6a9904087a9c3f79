"""Layer: expert layer. Rows the held dispatch's row movement passed over
(whole chunks up to the buffer's last live row, or the whole rung in the
every-expert form) over the rows of the form that ran, summed over the
expert layers — from the step's own ``dispatch_rows_walked`` and
``dispatch_rung`` buffers as the window's last dispatch left them (the
check hands the trainer its buffers back). 0.5 when loads are even and
the buffer is twice the even load. None for a system whose step has no
such buffer."""


def read(ctx):
    state = getattr(getattr(ctx["system"], "trainer", None), "state", None)
    buffers = (state or {}).get("buffers", {})
    if "dispatch_rows_walked" not in buffers or \
            "dispatch_rung" not in buffers:
        return None
    rung = float(buffers["dispatch_rung"].sum())
    return float(buffers["dispatch_rows_walked"].sum()) / rung if rung \
        else None
