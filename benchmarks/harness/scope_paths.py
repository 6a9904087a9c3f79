"""Device time under a scope ANYWHERE in an operation's name.

``harness/scopes.py`` gives an operation to the LAST ``pt.`` token of its
``op_name``, so that a kernel inside a block counts as the kernel. A scope
that says what KIND of block an operation sits in (``pt.attn.window``
round a windowed attention block, its projections, rotary, repeat and
kernels all inside) is never last; this reader asks whether the token is
in the name at all. Same text, same trace, same denominator (all
operation time); an instruction without an ``op_name`` counts under no
kind."""

from __future__ import annotations

from typing import Any, Dict, Optional

from harness import scopes, trace


def ops_under(hlo_text: str, token: str) -> Dict[str, bool]:
    """{instruction name: whether ``token`` is one of the ``pt.*`` tokens
    of its ``op_name``}."""
    out: Dict[str, bool] = {}
    for line in hlo_text.splitlines():
        im = scopes._INSTR_RE.match(line)
        if im:
            om = scopes._OP_NAME_RE.search(line)
            out[im.group(1)] = bool(om) and token in scopes.TOKEN_RE.findall(
                om.group(1))
    return out


def share_under(ctx: Dict[str, Any], token: str) -> Optional[float]:
    """Share of the traced operation time of the operations whose name
    holds ``token``; None without a trace, and where no operation of the
    compiled step holds it (an older program)."""
    red = ctx.get("trace")
    if not red or not red.get("op_self_s"):
        return None
    under = ops_under(scopes.step_text(ctx), token)
    if not any(under.values()):
        return None
    total = sum(red["op_self_s"].values())
    inside = sum(s for name, s in red["op_self_s"].items()
                 if under.get(trace.op_name(name)))
    return inside / total if total else None
