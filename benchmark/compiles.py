"""Counts compilations through jax.monitoring (copied from chip_smoke.py's
CompileCounter): jaxpr traces, backend compiles and persistent-cache
hits, so a run can show that nothing compiled inside its window and
whether its set-up found every program in the cache."""
from __future__ import annotations

from typing import Tuple

TRACE = "/jax/core/compile/jaxpr_trace_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    def __init__(self):
        from jax._src import monitoring

        self.traces = 0
        self.compiles = 0
        self.cache_hits = 0
        self.names = []
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **kw):
        if event == TRACE:
            self.traces += 1
            self.names.append(str(kw.get("fun_name", "?")))
        elif event == BACKEND:
            self.compiles += 1

    def _on_event(self, event, **kw):
        if event == CACHE_HIT:
            self.cache_hits += 1

    def mark(self) -> Tuple[int, int, int]:
        return self.traces, self.compiles, self.cache_hits

    def since(self, mark) -> Tuple[int, int, int]:
        t, c, h = mark
        return self.traces - t, self.compiles - c, self.cache_hits - h

    def names_since(self, mark) -> list:
        return sorted(set(self.names[mark[0]:]))
