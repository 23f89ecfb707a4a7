"""Seconds JAX spends tracing, lowering and compiling, from
``jax.monitoring``, and the persistent-cache hits and writes.

JAX records a cache miss only when it writes an entry, so a compile whose
executable is too small, too quick or too large to store counts as
neither.  In a run that reads its executables back from the cache,
``compile`` holds the cache load.
"""
from __future__ import annotations

import jax

EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
          "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
          "/jax/core/compile/backend_compile_duration": "compile"}
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                "/jax/compilation_cache/cache_misses": "writes"}


class CompileClock:
    def __init__(self):
        self.seconds = dict.fromkeys(EVENTS.values(), 0.0)
        self.counts = dict.fromkeys(EVENTS.values(), 0)
        self.cache = dict.fromkeys(CACHE_EVENTS.values(), 0)
        self.compiled = []   # names of the functions XLA compiled
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, fun_name=None, **_):
        if event in EVENTS:
            self.seconds[EVENTS[event]] += duration
            self.counts[EVENTS[event]] += 1
            if EVENTS[event] == "compile":
                self.compiled.append(str(fun_name))

    def _event(self, event, **_):
        if event in CACHE_EVENTS:
            self.cache[CACHE_EVENTS[event]] += 1

    def snapshot(self) -> dict:
        return {"seconds": dict(self.seconds), "counts": dict(self.counts),
                "cache": dict(self.cache), "compiled": list(self.compiled)}
