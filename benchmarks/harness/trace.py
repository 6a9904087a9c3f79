"""From a profiler trace to numbers: device busy and idle time, time per
operation, collective time and its exposed part, idle gaps by what the
host was doing.

``load_events`` flattens a JAX ``.xplane.pb`` into plain dicts
``{"plane", "line", "name", "start", "dur"}`` (seconds); every reduction
below works on that list, so it can be checked on a small recorded list
with known answers (``testdata/small_trace.json``, ``selfcheck.py``).

Conventions, fixed here so that every PR computes the same number:
- a device is a plane whose name starts with ``/device:TPU`` (or, in the
  recorded test trace, any ``/device:``); its operations are the events of
  its line ``XLA Ops``; the line ``Async XLA Ops`` holds the spans of
  asynchronous copies and collectives (start to done), which overlap the
  operations and are no time of the core's: they count for nothing but a
  collective's own duration;
- an operation's name is the HLO instruction as the profiler writes it
  (``%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), ...``); ``op_name`` cuts
  it to ``fusion.1`` and ``op_label`` to ``fusion.1 f32[8]``;
- an event that contains another event of its line (a ``while`` around a
  scan body) is a container: it has no time of its own beyond what its
  children leave, and it is no evidence that something else was running;
- busy time is the union of operation intervals, the window runs from the
  first operation's start to the last one's end, both per device; the
  reported numbers are means over the devices;
- a collective's exposed time is the part of its interval in which no
  other non-container operation runs on that device;
- host spans are the events named ``bench.*`` on host planes (the
  harness's own ``TraceAnnotation``s around feed, dispatch and sync).
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, Iterable, List, Tuple

Event = Dict[str, Any]
Interval = Tuple[float, float]

COLLECTIVE_RE = re.compile(
    r"^(all[-_]to[-_]all|all[-_]gather|all[-_]reduce|reduce[-_]scatter|"
    r"collective[-_]permute)")
HOST_SPAN_PREFIX = "bench."
OPS_LINE, ASYNC_LINE = "XLA Ops", "Async XLA Ops"
_NAME_RE = re.compile(r"^%?([\w.\-]+)")
_RESULT_RE = re.compile(r"=\s*\(?([a-z0-9]+\[[0-9,]*\])")


def op_name(event_name: str) -> str:
    """``%fusion.1 = f32[8]{0} fusion(...)`` -> ``fusion.1``."""
    m = _NAME_RE.match(event_name)
    return m.group(1) if m else event_name


def op_label(event_name: str) -> str:
    """``fusion.1 f32[8]``: the name with its (first) result shape."""
    m = _RESULT_RE.search(event_name)
    return op_name(event_name) + (" " + m.group(1) if m else "")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_events(xplane_path: str) -> Tuple[List[Event], Dict[str, Any]]:
    """(events, layout). Events: device operations and ``bench.*`` host
    spans only. ``layout`` says what the file held (planes, lines, event
    counts, a few names), for a human to look at once."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    events: List[Event] = []
    layout: Dict[str, Any] = {}
    for plane in data.planes:
        is_device = plane.name.startswith("/device:")
        lines = {}
        for line in plane.lines:
            evs = list(line.events)
            keep_ops = is_device and line.name in (OPS_LINE, ASYNC_LINE)
            sample = []
            for e in evs:
                if len(sample) < 3:
                    sample.append([e.name, sorted(k for k, _ in e.stats)])
                if keep_ops or (not is_device
                                and e.name.startswith(HOST_SPAN_PREFIX)):
                    events.append({"plane": plane.name, "line": line.name,
                                   "name": e.name,
                                   "start": e.start_ns * 1e-9,
                                   "dur": e.duration_ns * 1e-9})
            lines[line.name] = {"events": len(evs),
                                "sample": sample if is_device else []}
        layout[plane.name] = lines
    return events, layout


def _union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def _subtract(iv: Interval, cover: List[Interval]) -> float:
    """Length of ``iv`` not covered by the sorted disjoint ``cover``."""
    a, b = iv
    left = b - a
    for c, d in cover:
        if d <= a:
            continue
        if c >= b:
            break
        left -= min(b, d) - max(a, c)
    return max(left, 0.0)


def _with_self_time(line_events: List[Event]) -> List[Event]:
    """Per event of ONE line: ``self`` (duration minus what its children
    cover) and ``container`` (it has children)."""
    evs = sorted(line_events, key=lambda e: (e["start"], -e["dur"]))
    out, stack = [], []
    for e in evs:
        e = dict(e, self=e["dur"], container=False)
        end = e["start"] + e["dur"]
        while stack and stack[-1]["start"] + stack[-1]["dur"] <= e["start"]:
            stack.pop()
        if stack and end <= stack[-1]["start"] + stack[-1]["dur"] + 1e-12:
            stack[-1]["self"] -= e["dur"]
            stack[-1]["container"] = True
        stack.append(e)
        out.append(e)
    for e in out:
        e["self"] = max(e["self"], 0.0)
    return out


def reduce_trace(events: List[Event]) -> Dict[str, Any]:
    """Everything the per-layer readers need, in seconds and means over
    devices: ``busy_s``, ``window_s``, ``idle_share``, ``op_self_s``
    {event name: s, async spans left out}, ``collective_s``, ``collective_exposed_s``, ``idle_gaps``
    [[host span, s], ...] (device 0, longest first), ``devices``."""
    by_plane: Dict[str, Dict[str, List[Event]]] = {}
    host_spans: List[Event] = []
    for e in events:
        if e["plane"].startswith("/device:"):
            by_plane.setdefault(e["plane"], {}).setdefault(
                e["line"], []).append(e)
        elif e["name"].startswith(HOST_SPAN_PREFIX):
            host_spans.append(e)
    if not by_plane:
        return {"devices": 0}
    busy, window, coll, exposed = [], [], [], []
    op_self: Dict[str, float] = {}      # keyed by the event's full name
    gaps: List[Tuple[str, float]] = []  # of the first device
    for i, plane in enumerate(sorted(by_plane)):
        ops = _with_self_time(by_plane[plane].get(OPS_LINE, []))
        if not ops:
            continue
        spans = _union((e["start"], e["start"] + e["dur"]) for e in ops)
        busy.append(_total(spans))
        window.append(spans[-1][1] - spans[0][0])
        work = [e for e in ops if not e["container"]]
        for e in work:
            op_self[e["name"]] = op_self.get(e["name"], 0.0) + e["self"]
        # collectives: synchronous ones are operations; asynchronous ones
        # are a span on the async line between a -start and a -done
        # operation, which themselves are neither collective time (the
        # span has it) nor other work
        is_coll = lambda e: bool(COLLECTIVE_RE.match(op_name(e["name"])))
        others = _union((e["start"], e["start"] + e["dur"]) for e in work
                        if not is_coll(e))
        colls = [e for e in work if is_coll(e) and not re.search(
            r"-(start|done)(\.|$)", op_name(e["name"]))]
        colls += [e for e in by_plane[plane].get(ASYNC_LINE, [])
                  if is_coll(e)]
        coll.append(sum(e["dur"] for e in colls))
        exposed.append(sum(_subtract((e["start"], e["start"] + e["dur"]),
                                     others) for e in colls))
        if not gaps:
            for (_, a), (b, _) in zip(spans, spans[1:]):
                gaps.append((_host_span_over(host_spans, a, b), b - a))
    if not busy:
        return {"devices": 0}
    n = len(busy)
    mean = lambda xs: sum(xs) / n
    gaps.sort(key=lambda g: -g[1])
    return {
        "devices": n,
        "busy_s": mean(busy), "window_s": mean(window),
        "idle_share": 1.0 - mean(busy) / mean(window),
        "op_self_s": {k: v / n for k, v in op_self.items()},
        "collective_s": mean(coll), "collective_exposed_s": mean(exposed),
        "idle_gaps": [[name, s] for name, s in gaps],
    }


def _host_span_over(host_spans: List[Event], a: float, b: float) -> str:
    """Name (without the prefix) of the host span that covers most of the
    device gap [a, b]; ``host_other`` if none touches it."""
    best, best_s = "host_other", 0.0
    for h in host_spans:
        s = min(b, h["start"] + h["dur"]) - max(a, h["start"])
        if s > best_s:
            best, best_s = h["name"][len(HOST_SPAN_PREFIX):], s
    return best
