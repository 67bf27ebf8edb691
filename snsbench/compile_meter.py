"""Seconds and programs that JAX compiles, from ``jax.monitoring``.

A copy of the meter in the repository's ``chip_smoke.py``, kept with the
benchmark so that a change to the program cannot move it.  Compile time
is lowering plus backend compilation; tracing stays in the run time,
because nested jits trace inside their caller and would count twice.
"""
from __future__ import annotations

EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
          "/jax/core/compile/backend_compile_duration")


class CompileMeter:
    def __init__(self, jax_mod):
        self.seconds = 0.0
        self.compiles = 0
        jax_mod.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in EVENTS:
            self.seconds += duration
            self.compiles += event == EVENTS[-1]

    def snapshot(self):
        return self.seconds, self.compiles
