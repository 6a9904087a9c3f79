"""The flash kernels' share of their roofline under the EVA mask
(``evabyte-6.5b``: one call a layer over [summaries | keys], 32 heads of
128), beside ``harness/kernels_swa.py``, whose reader takes a band."""

from __future__ import annotations

from typing import Any, Dict, Optional

from harness import kernels


def roofline_percent(ctx: Dict[str, Any], kernel: str) -> Optional[float]:
    """100 x the step's REQUIRED calls of ``kernel`` — one a layer, each
    ``harness/flops_eva.flash_kernel_floor`` (from the mask's products) —
    over the kernel's measured device time a step: its time in the traced
    window (``kernels.flash_seconds``, by the ``pallas_call``'s own name)
    over the window's dispatches. The measured time holds every call the
    program makes: with ``recompute: blocks`` the forward kernel runs twice
    a layer, and the second run counts as time, never as work, so
    ``flash_fwd`` reads at most half of what one call would. None where the
    cell is no EVA configuration, on a rehearsal, or without the kernel in
    the trace."""
    cfg = ctx["cell"].config
    got = kernels.flash_seconds(ctx)
    if ctx["rehearse"] or not got or kernel not in got \
            or cfg.get("attention_class") != "eva":
        return None
    from harness import device, flops_eva

    system = ctx["system"]
    floor = cfg["num_hidden_layers"] * flops_eva.flash_kernel_floor(
        kernel, cfg, system.batch // ctx["chips"], system.seq,
        device.peaks(ctx["device_kind"]))["floor_s"]
    return 100.0 * floor / (got[kernel] / ctx["window"]["dispatches"])
