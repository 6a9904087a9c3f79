"""Host-side profiling annotations, and the names of the program's work.

Analogue of the reference's two-generation profiler
(``platform/profiler.cc`` RecordEvent scopes; ``platform/profiler/``
HostTracer + ChromeTracingLogger): a ``RecordEvent`` scope API that feeds
(a) ``jax.profiler`` trace annotations (so a span sits on the device
trace's clock whenever a profile is being taken — ``jax.profiler.
start_trace`` is the device profiler, this module wraps nothing round
it), (b) an ``obs.trace`` span while distributed tracing is on, (c) a
per-name wall-time aggregate (the reference's CostProfiler,
``distributed/common/cost_timer.h``) and (d) a bounded in-memory ring of
completed spans with parent ids and counts (``host_spans``).

One vocabulary, ``pt.*``: ``DEVICE_SCOPES`` are the ``jax.named_scope``
names inside the jitted steps (HLO metadata, trace-time only; an
operation belongs to the LAST ``pt.`` token of its ``op_name``);
``pt.pass.*`` are the host spans of the pass lifecycle
(``ps/embedding_cache.py``). docs/OPERATIONS.md §10 lists both.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from typing import Any, Dict, Iterator, List, NamedTuple

import jax

from ..obs import trace as _obs_trace

__all__ = [
    "RecordEvent",
    "timed",
    "record_event",
    "host_event_stats",
    "reset_host_events",
    "host_spans",
    "HostSpan",
    "DEVICE_SCOPES",
    "export_chrome_tracing",
    "start_timeline",
    "stop_timeline",
]

#: every ``jax.named_scope`` the program opens in a jitted step or round a
#: kernel. Names are HLO metadata: jax leaves metadata out of the
#: persistent compile-cache key, so an executable cached before a scope
#: existed comes back without it.
DEVICE_SCOPES = (
    "pt.unpack", "pt.probe", "pt.pull", "pt.tower", "pt.dense_opt",
    "pt.push.accumulate", "pt.push.update", "pt.route",
    "pt.embed", "pt.attn", "pt.ffn", "pt.head_loss", "pt.loss",
    "pt.flash_fwd", "pt.flash_bwd_dq", "pt.flash_bwd_dkv",
    "pt.rope", "pt.moe.route", "pt.moe.dispatch", "pt.moe.experts",
    "pt.moe.combine", "pt.moe.shared", "pt.mla.q", "pt.mla.kv", "pt.mtp",
    "pt.ffn.dense",
    "pt.conv", "pt.conv.in", "pt.conv.mix", "pt.conv.out",
    "pt.gqa.qkv", "pt.gqa.repeat",
)

#: completed spans kept in memory (newest win): a pass is about a dozen
#: spans, a step one, so this holds the last few thousand steps
SPAN_RING = 4096


class _HostEvents:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._count: Dict[str, int] = {}
        self._total: Dict[str, float] = {}
        self._max: Dict[str, float] = {}

    def add(self, name: str, seconds: float) -> None:
        with self._lock:
            self._count[name] = self._count.get(name, 0) + 1
            self._total[name] = self._total.get(name, 0.0) + seconds
            self._max[name] = max(self._max.get(name, 0.0), seconds)

    def stats(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {
                name: {
                    "count": self._count[name],
                    "total_s": self._total[name],
                    "avg_s": self._total[name] / self._count[name],
                    "max_s": self._max[name],
                }
                for name in self._count
            }

    def reset(self) -> None:
        with self._lock:
            self._count.clear()
            self._total.clear()
            self._max.clear()


class HostSpan(NamedTuple):
    """One completed ``RecordEvent``. ``t0`` is ``time.perf_counter``
    seconds (add ``obs.trace.EPOCH_ANCHOR_US`` for the wall clock);
    ``parent_id`` is the enclosing open ``RecordEvent`` of the same
    thread, 0 for a root."""

    name: str
    t0: float
    dur: float
    span_id: int
    parent_id: int
    tid: int
    counts: Dict[str, Any]


_EVENTS = _HostEvents()
_SPANS: collections.deque = collections.deque(maxlen=SPAN_RING)
_SPANS_LOCK = threading.Lock()
_IDS = itertools.count(1)          # next() is atomic under the GIL
_OPEN = threading.local()          # .stack: ids of this thread's open spans


def host_spans() -> List[HostSpan]:
    """The completed spans still in the ring, oldest first."""
    with _SPANS_LOCK:
        return list(_SPANS)


def start_timeline() -> None:
    """Forget the spans recorded so far, so that the next export holds
    only what follows (the legacy profiler's EnableProfiler analogue).
    Recording itself is always on."""
    with _SPANS_LOCK:
        _SPANS.clear()


def stop_timeline() -> None:
    """Kept for callers that bracket a region; the ring needs no stop."""


def export_chrome_tracing(path: str) -> str:
    """Dump the span ring in the chrome://tracing JSON format
    (chrometracing_logger.cc / tools/timeline.py output). Load via
    chrome://tracing or perfetto ui. Device-side traces come from
    ``jax.profiler.start_trace``'s XPlane dump instead."""
    import json

    events = [{
        "name": s.name, "ph": "X",
        "ts": s.t0 * 1e6,          # chrome tracing wants microseconds
        "dur": s.dur * 1e6, "pid": 0, "tid": s.tid,
        "args": dict(s.counts, span_id=s.span_id, parent_id=s.parent_id),
    } for s in host_spans()]
    # clockSyncUs: this process's wall anchor for its perf_counter
    # timestamps — tools/timeline.py aligns multi-worker lanes by it
    # instead of interleaving raw per-host monotonic clocks
    blob = {"traceEvents": events, "displayTimeUnit": "ms",
            "clockSyncUs": _obs_trace.EPOCH_ANCHOR_US}
    with open(path, "w") as f:
        json.dump(blob, f)
    return path


@contextlib.contextmanager
def RecordEvent(name: str, **counts) -> Iterator[Dict[str, Any]]:
    """Annotate a host scope; shows up in the jax.profiler trace, in
    ``host_event_stats()`` and in ``host_spans()``. Ops in the reference
    are auto-wrapped this way inside OperatorBase::Run (operator.cc);
    here users and the framework's train loops wrap logical phases
    (forward, backward, pull_sparse...).

    ``counts`` (numbers: keys, rows, bytes) ride the ``TraceAnnotation``
    and the span record. The scope yields that record's dict: a count
    known only once the work is done (``ev["bytes"] = a.nbytes``) is
    added there and reaches the record and the obs span, not the
    annotation, which is written at entry.

    While distributed tracing is on (``obs.trace.start_tracing``) every
    RecordEvent scope ALSO opens an obs span — the existing annotations
    (``pserver_client_pull_sparse``, ``ctr_train_step``, …) become the
    client side of the cross-process timeline for free; tracing off
    costs one module-bool check."""
    stack = getattr(_OPEN, "stack", None)
    if stack is None:
        stack = _OPEN.stack = []
    span_id = next(_IDS)
    parent_id = stack[-1] if stack else 0
    stack.append(span_id)
    obs = (_obs_trace.span(name) if _obs_trace.tracing_enabled()
           else contextlib.nullcontext())
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(name, **counts), obs as obs_span:
        try:
            yield counts
        finally:
            dt = time.perf_counter() - t0
            stack.pop()
            if obs_span is not None:
                for k, v in counts.items():
                    obs_span.add_attr(k, v)
            _EVENTS.add(name, dt)
            with _SPANS_LOCK:
                _SPANS.append(HostSpan(
                    name, t0, dt, span_id, parent_id,
                    threading.get_ident() % 1_000_000, counts))


record_event = RecordEvent


def host_event_stats() -> Dict[str, Dict[str, float]]:
    return _EVENTS.stats()


def reset_host_events() -> None:
    _EVENTS.reset()


def timed(fn, *args, iters: int = 20):
    """Per-call device time of ``fn(*args)``: one warm-up call (it
    compiles), then ``iters`` enqueued dispatches closed by ONE
    ``jax.block_until_ready`` — a chip runs enqueued programs in order,
    so the last output being ready bounds them all. Returns
    ``(seconds per call, last output)``.

    ``block_until_ready`` is the sync primitive: on a TPU v5e, 20 chained
    8192³ bf16 matmuls took 118.5 ms closed by it and 119.3 ms closed by
    a one-element device-to-host fetch, against 3.4 ms to enqueue them
    (chip run, PR 21 — the fetch only adds its own copy)."""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters, out
