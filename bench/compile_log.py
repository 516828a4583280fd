"""The split of set-up into host work, trace+lower and backend compiles,
from JAX's compile-duration events (copied from the chip smoke run, so
that the yardstick does not move when that script does)."""

from __future__ import annotations

from typing import List, Tuple

_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}


class CompileLog:
    """Trace, lowering and backend-compile durations, collected per phase.
    Register with ``jax.monitoring.register_event_duration_secs_listener``."""

    def __init__(self):
        self.events: List[Tuple[str, float]] = []
        self.total_compiles = 0

    def __call__(self, event, duration, *args, **kwargs):
        kind = _COMPILE_EVENTS.get(event)
        if kind is not None:
            self.events.append((kind, float(duration)))
            if kind == "compile":
                self.total_compiles += 1

    def report(self, phase: str, total_s: float, out) -> int:
        """Print the phase's set-up line to ``out``; returns its backend
        compiles (or persistent-cache loads)."""
        ev, self.events = self.events, []
        compiles = [d for k, d in ev if k == "compile"]
        other = sum(d for k, d in ev if k != "compile")
        # nested traces are counted twice, so the rest may round below 0
        rest = max(0.0, total_s - other - sum(compiles))
        print(f"setup [{phase}]: {total_s:.3f} s wall = host work "
              f"{rest:.3f} s + trace+lower {other:.3f} s + "
              f"{len(compiles)} backend compile(s) or cache load(s): "
              + ", ".join(f"{d:.3f} s" for d in compiles), file=out,
              flush=True)
        return len(compiles)
