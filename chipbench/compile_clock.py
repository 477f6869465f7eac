"""Compile time and compile count, summed from JAX's monitoring events.

Tracing, lowering and compiling (or fetching a compiled program from the
persistent cache) each report a duration event; a backend compile also
counts one compile.  Registered once, before the first compile.
"""

from __future__ import annotations

EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
BACKEND = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """``seconds``: time spent in the events above; ``compiles``: backend
    compiles (a persistent-cache hit reports none)."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0

    def __call__(self, event, duration, **_):
        if event in EVENTS:
            self.seconds += duration
        if event == BACKEND:
            self.compiles += 1

    def register(self):
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self)
        return self
