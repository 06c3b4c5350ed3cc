"""Seconds JAX spent tracing, lowering and compiling, from its own monitoring
events. A copy of ``chip_smoke.CompileClock`` (PR 24) with a count of backend
compiles added: the smoke may change, the yardstick may not."""

from __future__ import annotations

from typing import Any, Dict


class CompileClock:
    _KEYS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
        "/jax/core/compile/backend_compile_duration": "backend_compile_s",
        "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval_s",
    }

    def __init__(self) -> None:
        import jax.monitoring

        self.totals: Dict[str, float] = {v: 0.0 for v in self._KEYS.values()}
        self.totals["cache_hits"] = 0
        self.totals["backend_compiles"] = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_: Any) -> None:
        key = self._KEYS.get(event)
        if key is not None:
            self.totals[key] += secs
            if key == "backend_compile_s":
                self.totals["backend_compiles"] += 1

    def _event(self, event: str, **_: Any) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.totals["cache_hits"] += 1

    def snapshot(self) -> Dict[str, float]:
        return dict(self.totals)

    def since(self, before: Dict[str, float]) -> Dict[str, float]:
        return {k: self.totals[k] - before[k] for k in self.totals}
