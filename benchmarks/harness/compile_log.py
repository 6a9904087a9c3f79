"""Counts what JAX compiled (copy of ``chip_smoke.CompileLog``, PR 21).

Every backend compile request, and of those the persistent-cache hits and
the entries written. ``requests`` read at t0 and at t1 gives
``compiles_in_window``."""

from __future__ import annotations


class CompileLog:
    def __init__(self) -> None:
        import jax.monitoring as mon

        self.requests = 0
        self.hits = 0
        self.written = 0
        mon.register_event_listener(self._on_event)
        mon.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.written += 1

    def _on_duration(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
