"""Small measuring pieces the harness keeps as its own copies: counting
compiles from JAX's monitoring events, and the percentile arithmetic."""
from __future__ import annotations

import math

COMPILE_EVENTS = {
    "/jax/core/compile/backend_compile_duration": "compile_s",
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
}


class CompileClock:
    """Seconds JAX spends compiling (or loading from the persistent cache),
    tracing and lowering, the number of backend compiles, and persistent
    cache hits, summed from JAX's monitoring events."""

    def __init__(self, jax):
        self.totals = {k: 0.0 for k in COMPILE_EVENTS.values()}
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        key = COMPILE_EVENTS.get(event)
        if key:
            self.totals[key] += duration
            self.compiles += key == "compile_s"

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return dict(self.totals, compiles=self.compiles,
                    cache_hits=self.cache_hits)


def percentile(values, q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    rank = (len(xs) - 1) * q / 100.0
    lo = math.floor(rank)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)
