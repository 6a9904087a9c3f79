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

JAX's compile pipeline lands in (c) and (d) too: a ``jax.monitoring``
listener, installed once at import, records every outermost trace, every
lowering and every backend compile request (a persistent-cache read or
an XLA compile) as ``pt.compile.trace`` / ``pt.compile.lower`` /
``pt.compile`` spans with the function's name, under the ``RecordEvent``
that was open on that thread. A compiled function's next call fires
nothing, so the step path pays nothing.

One vocabulary, ``pt.*``: ``DEVICE_SCOPES`` are the ``jax.named_scope``
names inside the jitted steps (HLO metadata, trace-time only; an
operation belongs to the LAST ``pt.`` token of its ``op_name``);
``pt.pass.*`` are the host spans of the pass lifecycle
(``ps/embedding_cache.py``); ``pt.compile*`` are the compile pipeline's.
docs/OPERATIONS.md §10 lists all three.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from typing import Any, Dict, Iterator, List, NamedTuple

import jax
import jax.monitoring

from ..obs import registry as _obs_registry
from ..obs import trace as _obs_trace

__all__ = [
    "RecordEvent",
    "timed",
    "host_event_stats",
    "reset_host_events",
    "host_spans",
    "HostSpan",
    "DEVICE_SCOPES",
    "export_chrome_tracing",
    "start_timeline",
    "install_compile_listener",
    "compile_counts",
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
    "pt.attn.full", "pt.attn.window",
    "pt.eva.qkv", "pt.eva.prep",
    "pt.hc.map", "pt.hc.collect", "pt.hc.scatter",
)

#: completed spans kept in memory (newest win): a pass is about a dozen
#: spans, a step one, a compiled program three, so this holds the last few
#: thousand steps
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
    """One completed ``RecordEvent`` or ``pt.compile*`` span. ``t0`` is
    ``time.perf_counter`` seconds (add ``obs.trace.EPOCH_ANCHOR_US`` for
    the wall clock); ``parent_id`` is the enclosing open ``RecordEvent``
    of the same thread, 0 for a root."""

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
            _complete(name, t0, dt, span_id, parent_id, counts)


def _complete(name: str, t0: float, dur: float, span_id: int,
              parent_id: int, counts: Dict[str, Any]) -> None:
    _EVENTS.add(name, dur)
    with _SPANS_LOCK:
        _SPANS.append(HostSpan(name, t0, dur, span_id, parent_id,
                               threading.get_ident() % 1_000_000, counts))


# -- the compile pipeline, from jax.monitoring -----------------------------

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
#: jax fires it when it WRITES an entry: a program it compiled, not read
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

_REQUESTS = _obs_registry.counter("pt_compile_requests")
_CACHE_HITS = _obs_registry.counter("pt_compile_cache_hits")
_CACHE_MISSES = _obs_registry.counter("pt_compile_cache_misses")

#: this thread's place in the pipeline. ``.depth`` / ``.traces``: the
#: traces and lowerings open, and the traces finished since the outermost
#: of them was entered (inner jits and even ``add`` trace inside the outer
#: function; a lowering rule written in jax.numpy traces its operations
#: too: hundreds an initialiser); ``.hit`` / ``.cache_read_s``: what the
#: cache said since the backend request was entered
_COMPILING = threading.local()
_LISTENING = False


def _compile_span(name: str, start: float, end: float,
                  counts: Dict[str, Any]) -> None:
    """jax stamps its events with ``time.time()``; the ring's clock is
    ``perf_counter``, the anchor between the two is ``obs.trace``'s."""
    stack = getattr(_OPEN, "stack", None)
    _complete(name, start - _obs_trace.EPOCH_ANCHOR_US / 1e6, end - start,
              next(_IDS), stack[-1] if stack else 0, counts)


def _on_compile_entry(event: str, _start: float, **_kw) -> None:
    if event == _TRACE_EVENT or event == _LOWER_EVENT:
        depth = getattr(_COMPILING, "depth", 0)
        if depth == 0:
            _COMPILING.traces = 0
        _COMPILING.depth = depth + 1
    elif event == _BACKEND_EVENT:
        _COMPILING.hit = 0
        _COMPILING.cache_read_s = 0.0


def _on_cache_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT_EVENT:
        _COMPILING.hit = 1
        _CACHE_HITS.inc()
    elif event == _CACHE_MISS_EVENT:
        _CACHE_MISSES.inc()


def _on_cache_read(event: str, seconds: float, **_kw) -> None:
    if event == _CACHE_READ_EVENT:
        _COMPILING.cache_read_s = seconds


def _on_compile_exit(event: str, start: float, end: float,
                     fun_name: str = "", **_kw) -> None:
    if event == _TRACE_EVENT:
        _COMPILING.traces = getattr(_COMPILING, "traces", 0) + 1
        _COMPILING.depth = max(getattr(_COMPILING, "depth", 1) - 1, 0)
        if _COMPILING.depth == 0:     # else: inside that trace or lowering
            _compile_span("pt.compile.trace", start, end,
                          {"fun": fun_name, "traces": _COMPILING.traces})
    elif event == _LOWER_EVENT:
        _COMPILING.depth = max(getattr(_COMPILING, "depth", 1) - 1, 0)
        _compile_span("pt.compile.lower", start, end, {"fun": fun_name})
    elif event == _BACKEND_EVENT:
        _REQUESTS.inc()
        _compile_span("pt.compile", start, end, {
            "fun": fun_name, "hit": getattr(_COMPILING, "hit", 0),
            "cache_read_s": getattr(_COMPILING, "cache_read_s", 0.0)})


def install_compile_listener() -> bool:
    """Register the four ``jax.monitoring`` listeners above; False (and
    nothing registered) when they already are. Called at import."""
    global _LISTENING
    if _LISTENING:
        return False
    _LISTENING = True
    jax.monitoring.register_scalar_listener(_on_compile_entry)
    jax.monitoring.register_event_listener(_on_cache_event)
    jax.monitoring.register_event_duration_secs_listener(_on_cache_read)
    jax.monitoring.register_event_time_span_listener(_on_compile_exit)
    return True


install_compile_listener()


def compile_counts() -> Dict[str, int]:
    """This process's backend compile ``requests`` and, of those, the
    persistent cache's ``cache_hits`` and the entries it wrote
    (``cache_written``): the three ``pt_compile_*`` counters."""
    return {"requests": _REQUESTS.value, "cache_hits": _CACHE_HITS.value,
            "cache_written": _CACHE_MISSES.value}


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
