"""The flash kernels' share of their roofline where a step's calls differ
in their mask (``smallthinker-21b-a3b``: one global causal call and three
under a 4096-key window, 28 query / 4 key-value heads of 128), beside
``harness/kernels_gqa.py``, whose reader takes one mask for every call."""

from __future__ import annotations

from typing import Any, Dict, Optional

from harness import kernels


def roofline_percent(ctx: Dict[str, Any], kernel: str) -> Optional[float]:
    """100 x the sum over the step's calls of
    ``harness/flops_swa.flash_kernel_floor`` (each under its own layer's
    mask) over the kernel's measured device time a step: its time in the
    traced window (``kernels.flash_seconds``, by the ``pallas_call``'s own
    name, whatever mask the call carries) over the window's dispatches.
    None where the cell is no sliding-window configuration, on a
    rehearsal, or without the kernel in the trace."""
    cfg = ctx["cell"].config
    got = kernels.flash_seconds(ctx)
    if ctx["rehearse"] or not got or kernel not in got \
            or "sliding_window_layout" not in cfg:
        return None
    from harness import device, flops_swa

    system = ctx["system"]
    floor = flops_swa.step_flash_floor_s(
        kernel, cfg, system.batch // ctx["chips"], system.seq,
        device.peaks(ctx["device_kind"]))
    return 100.0 * floor / (got[kernel] / ctx["window"]["dispatches"])
