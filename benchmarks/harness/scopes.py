"""The program's own names, read back: device time per ``pt.*`` scope and
host time per ``pt.pass.*`` phase.

The program (``paddle_tpu/core/profiler.py``) opens ``jax.named_scope``s
named ``pt.<name>`` inside its jitted steps and round its kernels, and
records the phases of the pass lifecycle as ``RecordEvent`` spans named
``pt.pass.<phase>`` with a parent id. Named scopes are HLO metadata: the
compiled text carries them as ``metadata={op_name="jit(step)/.../pt.tower/
.../dot_general"}`` on every instruction, and the profiler's ``XLA Ops``
events carry the instruction's name. (They carry no ``op_name`` stat of
their own on this runtime — device_offset_ps, device_duration_ps and a
time-scale multiplier are all an event has, chip run PR 24 — so the join
goes through the compiled text.)

Rules, fixed here so that every PR computes the same number:
- an operation belongs to the LAST ``pt.`` token of its ``op_name``
  (``transpose(jvp(pt.tower))/dot_general`` is ``pt.tower``; a kernel's
  ``pt.flash_fwd`` inside ``pt.attn`` wins over it); no token = unscoped;
- a fused operation belongs to the scope XLA kept on the fusion's own
  instruction, whatever it fused into it; only where XLA kept no
  ``op_name`` at all does it take the commonest scope of what it calls;
- shares are over ALL operation time of the trace (``op_self_s``, the
  denominator ``table_sweep_share`` uses), so the scopes and the unscoped
  rest sum to 1;
- a program without the scopes (an older commit) gives ``None``, never 0;
- the pass a cell measures is the ``pt.pass.begin`` root with the most
  ``unique_keys`` (``pt.pass.end``: the most ``keys``): the ``correct``
  check builds small caches of its own in the same process.
"""

from __future__ import annotations

import json
import re
import sys
from typing import Any, Dict, List, Optional

# a scope's name: ``pt.`` at the start of a word (not ``opt.step``), then
# lower-case words joined by dots
TOKEN_RE = re.compile(r"(?<![\w.])pt\.[a-z_]+(?:\.[a-z_]+)*")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_CALLS_RE = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")
UNSCOPED = "unscoped"
_SAID = set()


def scope_of(op_name: str) -> Optional[str]:
    """Last ``pt.*`` token of one ``op_name``; None without one."""
    found = TOKEN_RE.findall(op_name)
    return found[-1] if found else None


def scope_of_ops(hlo_text: str) -> Dict[str, Optional[str]]:
    """{instruction name: its scope, None if unscoped} for every
    instruction of a compiled module's text. An instruction that carries
    no ``op_name`` at all (XLA's scatter and sort rewrites make such
    fusions) takes the commonest scope among the instructions of the
    computations it calls, found the same way."""
    comps: Dict[str, List[str]] = {}        # computation -> its instructions
    own: Dict[str, Optional[str]] = {}      # instruction -> op_name or None
    calls: Dict[str, List[str]] = {}        # instruction -> computations
    current: List[str] = []
    for line in hlo_text.splitlines():
        cm = _COMP_RE.match(line)
        if cm:
            current = comps.setdefault(cm.group(1), [])
            continue
        im = _INSTR_RE.match(line)
        if im:
            name = im.group(1)
            om = _OP_NAME_RE.search(line)
            own[name] = om.group(1) if om else None
            calls[name] = _CALLS_RE.findall(line)
            current.append(name)
    out: Dict[str, Optional[str]] = {}

    def resolve(name: str) -> Optional[str]:
        if name not in out:
            out[name] = None                # a cycle cannot happen; be safe
            if own[name] is not None:
                out[name] = scope_of(own[name])
            else:
                inner = [resolve(i) for c in calls[name]
                         for i in comps.get(c, [])]
                inner = [x for x in inner if x]
                if inner:
                    out[name] = max(sorted(set(inner)), key=inner.count)
        return out[name]

    for name in own:
        resolve(name)
    return out


def _why_none(why: str) -> None:
    print(f"scopes: no share reported: {why}", file=sys.stderr, flush=True)


def _program_scopes():
    """The program's ``DEVICE_SCOPES``; None for a program without them."""
    from paddle_tpu.core import profiler

    return getattr(profiler, "DEVICE_SCOPES", None)


def _step_text(ctx: Dict[str, Any]) -> str:
    """Compiled text of the dispatched step: what the adapter handed over,
    or, where it hands over none (the dense adapter), the program's own
    ``Trainer.compiled_text`` on one of the system's items."""
    system = ctx["system"]
    if getattr(system, "trainer", None) is None:
        return system.compiled_text()
    fn = getattr(system.trainer, "compiled_text", None)
    return fn(*system.host_items[0]) if fn else ""


def step_text(ctx: Dict[str, Any]) -> str:
    text = ctx.get("hlo_text") or _step_text(ctx)
    if TOKEN_RE.search(text) or not _program_scopes():
        return text
    # The program opens scopes and the executable names none: it came out
    # of a compile cache that an older commit filled (jax leaves metadata
    # out of the cache key). Compile once more, past every cache, for a
    # text with the names; the instruction names are those that ran.
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    _why_none("the cached executable predates the scopes; compiling anew "
              "for its text")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    jax.clear_caches()
    try:
        return _step_text(ctx)
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def shares_of(op_self_s: Dict[str, float],
              scopes: Dict[str, Optional[str]]) -> Dict[str, Any]:
    """{"shares": {scope or "unscoped": share of all operation time},
    "ops": [[label, scope, seconds], ...] costliest first,
    "unscoped_ops": [[label, seconds], ...] likewise}."""
    from harness import trace

    total = sum(op_self_s.values())
    by: Dict[str, float] = {}
    ops: List[List[Any]] = []
    for event_name, s in op_self_s.items():
        scope = scopes.get(trace.op_name(event_name)) or UNSCOPED
        by[scope] = by.get(scope, 0.0) + s
        ops.append([trace.op_label(event_name), scope, s])
    ops.sort(key=lambda o: -o[2])
    return {"shares": {k: v / total for k, v in by.items()} if total else {},
            "ops": ops,
            "unscoped_ops": [[o[0], o[2]] for o in ops if o[1] == UNSCOPED]}


def scope_shares(ctx: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """Share of the traced operation time per scope, ``"unscoped"``
    included; None when there is no trace or the program has no scope.
    Computed once a run (kept on ``ctx``); prints the split, the costliest
    operations with their scopes and the costliest unscoped operations as
    an earlier line of stdout."""
    if "_scope_shares" in ctx:
        return ctx["_scope_shares"]
    ctx["_scope_shares"] = None
    red = ctx.get("trace")
    if not red or not red.get("op_self_s"):
        return None
    if not _program_scopes():
        _why_none("this program declares no DEVICE_SCOPES")
        return None
    scopes = scope_of_ops(step_text(ctx))
    if not any(scopes.values()):
        _why_none("no instruction of the compiled step carries a pt.* token")
        return None
    got = shares_of(red["op_self_s"], scopes)
    print(json.dumps({"scope_shares": got["shares"],
                      "scoped_ops": got["ops"][:16],
                      "unscoped_ops": got["unscoped_ops"][:15]}), flush=True)
    ctx["_scope_shares"] = got["shares"]
    return got["shares"]


def share(ctx: Dict[str, Any], *names: str,
          prefix: Optional[str] = None) -> Optional[float]:
    """Sum of the shares of ``names`` (and of every scope starting with
    ``prefix``); None when no shares can be read. A scope that no
    operation carries counts 0: the step was read, and it is not there."""
    shares = scope_shares(ctx)
    if shares is None:
        return None
    return sum(v for k, v in shares.items()
               if k in names or (prefix and k.startswith(prefix)))


def pick_root(spans, which: str):
    """The ``pt.pass.<which>`` root of the cell's own pass: most
    ``unique_keys`` (begin) or ``keys`` (end)."""
    count = {"begin": "unique_keys", "end": "keys"}[which]
    roots = [s for s in spans if s.name == "pt.pass." + which]
    return max(roots, key=lambda s: s.counts.get(count, 0), default=None)


def pass_phases(which: str) -> Optional[Dict[str, float]]:
    """{phase: seconds} of the direct children of the cell's
    ``pt.pass.begin`` / ``pt.pass.end`` root (``"begin"`` / ``"end"``),
    the prefix cut; None when the program records no such span."""
    from paddle_tpu.core import profiler

    if not hasattr(profiler, "host_spans"):
        return None
    spans = profiler.host_spans()
    root = pick_root(spans, which)
    if root is None:
        return None
    out: Dict[str, float] = {}
    for s in spans:
        if s.parent_id == root.span_id:
            key = s.name[len("pt.pass."):] if s.name.startswith(
                "pt.pass.") else s.name
            out[key] = out.get(key, 0.0) + s.dur
    out["_root"] = root.dur
    if which not in _SAID:      # once a run, as an earlier line of stdout
        _SAID.add(which)
        print(json.dumps({"pass_phases": {
            "root": root.name, "counts": root.counts, "seconds": out}}),
            flush=True)
    return out


def phase_seconds(which: str, *phases: str) -> Optional[float]:
    """Sum of the named phases; None unless the root and all of them were
    recorded."""
    got = pass_phases(which)
    if got is None or any(p not in got for p in phases):
        return None
    return sum(got[p] for p in phases)
