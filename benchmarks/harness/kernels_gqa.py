"""The flash kernels' share of their roofline at grouped-query widths
(``lfm2-8b-a1b``: 32 query / 8 key-value heads of 64, causal), beside
``harness/kernels.py``, whose reader takes the latent widths."""

from __future__ import annotations

from typing import Any, Dict, Optional

from harness import kernels


def roofline_percent(ctx: Dict[str, Any], kernel: str) -> Optional[float]:
    """100 x ``harness/flops_lfm2.flash_kernel_floor`` of one call over the
    kernel's measured time a call: its time in the traced window
    (``kernels.flash_seconds``, by the ``pallas_call``'s own name) over
    the window's dispatches x the attention blocks. None where the cell
    is no conv-hybrid configuration, on a rehearsal, or without the
    kernel in the trace."""
    cfg = ctx["cell"].config
    got = kernels.flash_seconds(ctx)
    if ctx["rehearse"] or not got or kernel not in got \
            or "conv_L_cache" not in cfg:
        return None
    from harness import device, flops_lfm2

    system = ctx["system"]
    blocks = flops_lfm2.layer_kinds(cfg).count("full_attention")
    calls = ctx["window"]["dispatches"] * blocks
    floor = flops_lfm2.flash_kernel_floor(
        kernel, cfg, system.batch // ctx["chips"], system.seq,
        device.peaks(ctx["device_kind"]))
    return 100.0 * floor["floor_s"] / (got[kernel] / calls)
