"""Device time of the flash-attention kernels in a traced window, by the
kernels' own names (``ops/flash_attention.py`` gives each ``pallas_call``
a ``name``; the profiler's operation is ``%flash_fwd.3 = ...``)."""

from __future__ import annotations

import re
from typing import Any, Dict, Optional

#: a flash kernel's operation in a trace, by the ``name`` of its call
KERNEL_RE = re.compile(r"^%?(flash_fwd|flash_bwd_dq|flash_bwd_dkv)\b")


def flash_seconds(ctx: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """{kernel: seconds over the traced window}; None without a trace or
    where the trace holds no such operation."""
    red = ctx.get("trace")
    if not red or not red.get("op_self_s"):
        return None
    out: Dict[str, float] = {}
    for name, s in red["op_self_s"].items():
        m = KERNEL_RE.match(name)
        if m:
            out[m.group(1)] = out.get(m.group(1), 0.0) + s
    return out or None


def mla_roofline_share(ctx: Dict[str, Any], kernel: str) -> Optional[float]:
    """``harness/flops_mla.flash_kernel_floor`` of one call over the
    kernel's measured time a call: its time in the window over the
    window's dispatches x the blocks with attention. None where the cell
    is no latent-attention configuration, on a rehearsal, or without the
    kernel in the trace."""
    cfg = ctx["cell"].config
    got = flash_seconds(ctx)
    if ctx["rehearse"] or not got or kernel not in got \
            or "qk_rope_head_dim" not in cfg:
        return None
    from harness import device, flops_mla

    system = ctx["system"]
    calls = ctx["window"]["dispatches"] * flops_mla.attention_blocks(cfg)
    floor = flops_mla.flash_kernel_floor(
        kernel, cfg, system.batch // ctx["chips"], system.seq,
        device.peaks(ctx["device_kind"]))
    return floor["floor_s"] / (got[kernel] / calls)
