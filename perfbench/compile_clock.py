"""Backend compiles counted from JAX's monitoring events."""
from __future__ import annotations

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Seconds the XLA backend spends compiling while entered, and the
    number of compiles (tracing and lowering are not counted)."""

    def __enter__(self):
        import jax
        self.seconds, self.compiles = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_event)

    def _on_event(self, event: str, secs: float, **_):
        if event == _COMPILE_EVENT:
            self.seconds += secs
            self.compiles += 1
