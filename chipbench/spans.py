"""Host spans recorded from the benchmark's own files, around its calls into
the program, and a count of compilations.

Each span is also a ``jax.profiler.TraceAnnotation``, so a traced run has
it on the same clock as the device's operations and the trace reduction
can say what the host was doing in each idle gap.
"""

from __future__ import annotations

import contextlib
import threading
import time

import jax
from jax import monitoring

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class Spans:
    """Thread-safe list of ``(name, start_s, end_s)`` on ``time.perf_counter``."""

    def __init__(self):
        self._lock = threading.Lock()
        self.items: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            t1 = time.perf_counter()
            with self._lock:
                self.items.append((name, t0, t1))


class CompileCounter:
    """Counts XLA backend compilations in this process (``count``) and the
    programs loaded from the persistent compilation cache (``loads``)."""

    def __init__(self):
        self.count = 0
        self.loads = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, *args, **kwargs) -> None:
        if event == _COMPILE_EVENT:
            self.count += 1

    def _on_event(self, event: str, *args, **kwargs) -> None:
        if event == _CACHE_HIT_EVENT:
            self.loads += 1
