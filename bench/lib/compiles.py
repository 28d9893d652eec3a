"""Counts lowerings and real XLA compilations while switched on.

A lowering is counted on every ``jaxpr -> MLIR`` conversion, also when
the persistent cache then serves the executable. A compilation is a
backend compile that the persistent cache did not serve: backend compile
events less persistent-cache hits. The measured window must show no
compilation; lowerings inside it are a per-call host cost of the program
and are reported beside it.
"""
from __future__ import annotations

import jax

LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    def __init__(self):
        self.lowerings = 0
        self.backend = 0
        self.cache_hits = 0
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, _secs, **_kw):
        if not self.on:
            return
        if name == LOWER_EVENT:
            self.lowerings += 1
        elif name == BACKEND_EVENT:
            self.backend += 1

    def _event(self, name, **_kw):
        if self.on and name == CACHE_HIT_EVENT:
            self.cache_hits += 1

    @property
    def compiles(self) -> int:
        return max(0, self.backend - self.cache_hits)
