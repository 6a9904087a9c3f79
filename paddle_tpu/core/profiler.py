"""Host-side profiling annotations.

Analogue of the reference's two-generation profiler
(``platform/profiler.cc`` RecordEvent scopes; ``platform/profiler/``
HostTracer + ChromeTracingLogger): a ``RecordEvent`` scope API that feeds
both (a) ``jax.profiler`` trace annotations (→ XPlane/perfetto, the TPU
replacement for CUPTI+chrome://tracing) and (b) a lightweight in-process
host-event aggregator for per-scope wall-time statistics, mirroring the
reference's CostProfiler (``distributed/common/cost_timer.h``).
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, List, Optional

import jax

from ..obs import trace as _obs_trace

__all__ = [
    "RecordEvent",
    "timed",
    "record_event",
    "profiler_enabled",
    "start_profiler",
    "stop_profiler",
    "host_event_stats",
    "reset_host_events",
    "export_chrome_tracing",
    "start_timeline",
    "stop_timeline",
    "CostTimer",
]


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


_EVENTS = _HostEvents()
_TRACING = threading.Event()
_TRACE_DIR: List[Optional[str]] = [None]


class _Timeline:
    """Complete-event recording for the ChromeTracingLogger export
    (platform/profiler/dump/chrometracing_logger.cc): one "X" (complete)
    event per RecordEvent scope with thread id, start, duration."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.enabled = False
        self.events: List[Dict] = []

    def add(self, name: str, t0: float, dur: float) -> None:
        if not self.enabled:
            return
        with self._lock:
            self.events.append({
                "name": name,
                "ph": "X",
                "ts": t0 * 1e6,          # chrome tracing wants microseconds
                "dur": dur * 1e6,
                "pid": 0,
                "tid": threading.get_ident() % 1_000_000,
            })


_TIMELINE = _Timeline()


def start_timeline() -> None:
    """Begin recording host RecordEvent scopes for chrome://tracing
    export (the legacy profiler's EnableProfiler analogue)."""
    _TIMELINE.events.clear()
    _TIMELINE.enabled = True


def stop_timeline() -> None:
    _TIMELINE.enabled = False


def export_chrome_tracing(path: str) -> str:
    """Dump recorded host events in the chrome://tracing JSON format
    (chrometracing_logger.cc / tools/timeline.py output). Load via
    chrome://tracing or perfetto ui. Device-side traces come from
    start_profiler()'s XPlane dump instead."""
    import json

    with _TIMELINE._lock:
        events = list(_TIMELINE.events)
    # clockSyncUs: this process's wall anchor for its perf_counter
    # timestamps — tools/timeline.py aligns multi-worker lanes by it
    # instead of interleaving raw per-host monotonic clocks
    blob = {"traceEvents": events, "displayTimeUnit": "ms",
            "clockSyncUs": _obs_trace.EPOCH_ANCHOR_US}
    with open(path, "w") as f:
        json.dump(blob, f)
    return path


@contextlib.contextmanager
def RecordEvent(name: str):
    """Annotate a host scope; shows up in the jax.profiler trace and in
    ``host_event_stats()``. Ops in the reference are auto-wrapped this way
    inside OperatorBase::Run (operator.cc); here users and the framework's
    train loops wrap logical phases (forward, backward, pull_sparse...).

    While distributed tracing is on (``obs.trace.start_tracing``) every
    RecordEvent scope ALSO opens an obs span — the existing annotations
    (``pserver_client_pull_sparse``, ``ctr_train_step``, …) become the
    client side of the cross-process timeline for free; tracing off
    costs one module-bool check."""
    t0 = time.perf_counter()
    obs = (_obs_trace.span(name) if _obs_trace.tracing_enabled()
           else contextlib.nullcontext())
    with jax.profiler.TraceAnnotation(name), obs:
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            _EVENTS.add(name, dt)
            _TIMELINE.add(name, t0, dt)


record_event = RecordEvent


class CostTimer:
    """Reference ``CostTimer`` (cost_timer.h:29): explicit start/stop timer
    feeding the same aggregator, for non-scope-shaped measurement."""

    def __init__(self, name: str) -> None:
        self._name = name
        self._t0 = time.perf_counter()

    def stop(self) -> float:
        dt = time.perf_counter() - self._t0
        _EVENTS.add(self._name, dt)
        return dt


def start_profiler(log_dir: str = "/tmp/paddle_tpu_trace") -> None:
    """Start a jax.profiler trace (XPlane; view with tensorboard/perfetto)."""
    if _TRACING.is_set():
        return
    jax.profiler.start_trace(log_dir)
    _TRACE_DIR[0] = log_dir
    _TRACING.set()


def stop_profiler() -> Optional[str]:
    if not _TRACING.is_set():
        return None
    jax.profiler.stop_trace()
    _TRACING.clear()
    return _TRACE_DIR[0]


def profiler_enabled() -> bool:
    return _TRACING.is_set()


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
