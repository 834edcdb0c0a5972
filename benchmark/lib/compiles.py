"""A ledger of JAX's compile events in this process (``jax.monitoring``):
seconds of tracing, lowering and XLA compiling, persistent-cache hits and
misses, and every compile that happens while a measured window is open
(there should be none)."""

from __future__ import annotations

from collections import defaultdict

_NAMES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read_s",
}
_COUNTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
           "/jax/compilation_cache/cache_misses": "cache_misses"}


class Ledger:
    def __init__(self) -> None:
        self.totals: dict[str, float] = defaultdict(float)
        self.in_window: list[tuple[str, str, float]] = []
        self.window_open = False

    def install(self) -> "Ledger":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def _duration(self, event: str, secs: float, **kw) -> None:
        name = _NAMES.get(event)
        if name is None:
            return
        self.totals[name] += secs
        if self.window_open and name != "cache_read_s":
            self.in_window.append((name, str(kw.get("fun_name", "?")), secs))

    def _event(self, event: str, **kw) -> None:
        name = _COUNTS.get(event)
        if name is not None:
            self.totals[name] += 1

    def summary(self) -> dict:
        return {k: round(v, 3) for k, v in sorted(self.totals.items())}


#: the process's ledger, installed by the harness before the first compile
LEDGER = Ledger()
