"""Where ``setup_s`` went, from the program's own compile spans.

The program records JAX's compile pipeline in its span ring
(``paddle_tpu.core.profiler.host_spans``, the ring ``harness/scopes.py``
reads ``pt.pass.*`` from): ``pt.compile.trace`` {``fun``, ``traces``} an
outermost traced function, ``pt.compile.lower`` {``fun``} a lowering,
``pt.compile`` {``fun``, ``hit``, ``cache_read_s``} a backend compile
request — ``hit`` 1 read from the persistent cache, 0 compiled by XLA. A
span's ``t0`` is ``perf_counter`` seconds, the clock of the window's
``t0``. The ``setup_*`` metrics sum, by kind, the spans that START before
``t0``.

They sum SELF seconds: an eager operation inside a traced function is
lowered and compiled while that function's trace is open, so its spans
start inside the trace's and are taken out of it (likewise whatever
starts inside a lowering). Nesting is per thread; spans of a second
thread may overlap the main thread's, and then the kinds can sum past the
wall clock — ``off_thread_s`` in the line this prints says by how much at
most.

A program with no ``pt.compile*`` span at all (one older than the spans)
reads None for every metric here; one with them reads a number, 0.0 where
nothing of the kind happened before ``t0``.
"""

from __future__ import annotations

import json
import threading
from typing import Any, Dict, List, Optional, Tuple

TRACE, LOWER, COMPILE = "pt.compile.trace", "pt.compile.lower", "pt.compile"
KINDS = (TRACE, LOWER, COMPILE)
TOP = 5


def self_seconds(spans: List[Any]) -> List[Tuple[Any, float]]:
    """(span, its duration less the spans of ``spans`` that start inside
    it and inside no span between) for each of ``spans``, per thread."""
    out: List[Tuple[Any, float]] = []
    by_tid: Dict[int, List[Any]] = {}
    for s in spans:
        by_tid.setdefault(s.tid, []).append(s)
    for group in by_tid.values():
        group.sort(key=lambda s: (s.t0, -s.dur))
        own = [s.dur for s in group]
        open_: List[int] = []       # indices of the spans s starts inside
        for i, s in enumerate(group):
            while open_ and s.t0 >= group[open_[-1]].t0 + group[open_[-1]].dur:
                open_.pop()
            if open_:
                own[open_[-1]] -= s.dur
            open_.append(i)
        out.extend((s, max(own[i], 0.0)) for i, s in enumerate(group))
    return out


def before_t0(ctx: Dict[str, Any]) -> Optional[List[Tuple[Any, float]]]:
    """The ``pt.compile*`` spans that start before the window's ``t0``,
    each with its self seconds; None when the program records none. Read
    once a run (kept on ``ctx``), and said as an earlier line of stdout
    with the ring's fill and the costliest functions of each kind."""
    if "_setup_spans" in ctx:
        return ctx["_setup_spans"]
    ctx["_setup_spans"] = None
    from paddle_tpu.core import profiler

    if not hasattr(profiler, "host_spans"):
        return None
    ring = profiler.host_spans()
    spans = [s for s in ring if s.name in KINDS]
    if not spans:
        return None
    t0 = ctx["window"]["t0"]
    got = [(s, own) for s, own in self_seconds(spans) if s.t0 < t0]
    ctx["_setup_spans"] = got
    me = threading.get_ident() % 1_000_000      # the ring's thread ids
    said: Dict[str, Any] = {
        "ring": len(ring), "ring_max": getattr(profiler, "SPAN_RING", None),
        "compile_spans": len(spans), "before_t0": len(got),
        "off_thread_s": sum(own for s, own in got if s.tid != me),
        "native_build": [[s.counts.get("built"), s.dur] for s in ring
                         if s.name == "pt.native.build" and s.t0 < t0]}
    for label, name, hit in (("trace", TRACE, None), ("lower", LOWER, None),
                             ("compile", COMPILE, 0),
                             ("cache_read", COMPILE, 1)):
        rows = [(s, own) for s, own in got if _is(s, name, hit)]
        rows.sort(key=lambda r: -r[1])
        said[label] = {"spans": len(rows), "self_s": sum(o for _, o in rows),
                       "top": [[s.counts.get("fun"), o]
                               for s, o in rows[:TOP]]}
    print(json.dumps({"setup_spans": said}), flush=True)
    return got


def _is(span: Any, name: str, hit: Optional[int]) -> bool:
    return span.name == name and (
        hit is None or int(span.counts.get("hit", 0)) == hit)


def seconds(ctx: Dict[str, Any], name: str,
            hit: Optional[int] = None) -> Optional[float]:
    """Self seconds of the spans called ``name`` (and, for ``pt.compile``,
    of that ``hit``) that start before ``t0``; None without the spans."""
    got = before_t0(ctx)
    if got is None:
        return None
    return sum(own for s, own in got if _is(s, name, hit))


def count(ctx: Dict[str, Any], name: str,
          hit: Optional[int] = None) -> Optional[int]:
    got = before_t0(ctx)
    if got is None:
        return None
    return sum(1 for s, _ in got if _is(s, name, hit))
