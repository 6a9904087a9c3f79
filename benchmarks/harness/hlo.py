"""Collective wire bytes and instruction shapes from compiled HLO text.

``report`` is the collective count of ``tools/hlo_bytes.py`` (sound; copied
without its conditional-branch tracking, which nothing here reads; the
original stays for its own tests — see PERF.md Open questions). ``instruction_dims``
is new: for every instruction name, the largest leading dimension among
its result and operand shapes, which is how a trace op is recognised as a
sweep over the whole table without trusting a fusion's number."""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1,
    "f8e5m2": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather", "all-to-all",
                "collective-permute")

# one typed buffer: dtype[d0,d1,...]{layout} — layout/suffixes optional
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
# an instruction line: %name = <result-type> opcode(...)
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s+"
                       r"([a-z][\w\-]*)\(")
_GROUPS_EXPLICIT_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _buffer_bytes(type_str: str) -> tuple:
    """(total bytes, first dtype, first shape) over every typed buffer in
    a result-type string (handles tuples)."""
    total, dtype, shape = 0, None, None
    for m in _SHAPE_RE.finditer(type_str):
        d, dims = m.group(1), m.group(2)
        if d not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for x in dims.split(","):
                n *= int(x)
        total += n * _DTYPE_BYTES[d]
        if dtype is None:
            dtype, shape = d, [int(x) for x in dims.split(",")] if dims else []
    return total, dtype, shape


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_EXPLICIT_RE.search(line)
    if m:
        ids = [x for x in m.group(1).split(",") if x.strip() != ""]
        return max(len(ids), 1)
    m = _GROUPS_IOTA_RE.search(line)
    if m:  # [G,S]<=[N]: G groups of size S
        return max(int(m.group(2)), 1)
    return default


def _wire_bytes(op: str, operand: int, result: int, n: int) -> float:
    if n <= 1:
        return 0.0
    f = (n - 1) / n
    if op == "all-reduce":
        return 2.0 * f * result
    if op == "reduce-scatter":
        return f * operand
    if op == "all-gather":
        return f * result
    if op == "all-to-all":
        return f * operand
    return float(operand)   # collective-permute


def report(hlo_text: str, num_devices: Optional[int] = None) -> Dict[str, Any]:
    """Every collective of one HLO module's text (``-start`` forms folded
    in, ``-done`` skipped) with its payload and the ring estimate of the
    bytes one participant moves."""
    collectives: List[Dict[str, Any]] = []
    for line in hlo_text.splitlines():
        im = _INSTR_RE.match(line)
        if not im:
            continue
        result_type, opcode = im.group(2), im.group(3)
        base = opcode[:-6] if opcode.endswith("-start") else opcode
        if base not in _COLLECTIVES or opcode.endswith("-done"):
            continue
        res_bytes, dtype, shape = _buffer_bytes(result_type)
        # operand buffers: typed buffers inside the (...) args
        args = line[im.end():]
        op_bytes, _, _ = _buffer_bytes(args.split(", channel_id")[0]
                                       .split(", replica_groups")[0])
        op_bytes = op_bytes or res_bytes
        n = _group_size(line, num_devices or 1)
        collectives.append({
            "op": base, "dtype": dtype, "shape": shape,
            "result_bytes": res_bytes, "operand_bytes": op_bytes,
            "group_size": n,
            "wire_bytes": _wire_bytes(base, op_bytes, res_bytes, n)})
    return {"n_collectives": len(collectives), "collectives": collectives,
            "wire_bytes_total": sum(c["wire_bytes"] for c in collectives)}


def instruction_dims(hlo_text: str) -> Dict[str, int]:
    """{instruction name: largest leading dimension of any array on its
    line (result and operands)}."""
    out: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        im = _INSTR_RE.match(line)
        if not im:
            continue
        lead = 0
        # stop before called-computation / metadata attributes
        text = line.split(", metadata=")[0]
        for m in _SHAPE_RE.finditer(text):
            if m.group(1) in _DTYPE_BYTES and m.group(2):
                lead = max(lead, int(m.group(2).split(",")[0]))
        out[im.group(1)] = max(out.get(im.group(1), 0), lead)
    return out

