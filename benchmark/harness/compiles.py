"""Count XLA compilations, so that none hides inside a measured window."""

from __future__ import annotations

_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == _EVENT:
            self.count += 1
