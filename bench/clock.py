"""Compile accounting from JAX's own monitoring events.

``CompileClock`` is copied from the program's ``chip_smoke.py``: it sums
the seconds JAX reports for tracing, lowering, compiling and persistent
cache reads, and counts the programs that were compiled or read back
from the cache.  The harness reads it at the window's edges: a window in
which any program is compiled or loaded fails the run.
"""

from __future__ import annotations

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
)
# One of these per program compiled, or read back from the cache.
_PROGRAM_EVENTS = (
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
)


class CompileClock:
    """Sums JAX's compile-time events; ``programs`` counts executables
    compiled or loaded from the persistent cache."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration
            self.programs += event in _PROGRAM_EVENTS
