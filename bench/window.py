"""The measured window, and the host spans the harness records.

``Window.begin()`` marks the end of set-up and, in a traced run, starts the
profiler and opens the ``bench.window`` span; ``Window.end()`` closes both.
``span(name)`` is a ``jax.profiler.TraceAnnotation`` while a trace runs and
costs nothing otherwise.
"""
from __future__ import annotations

import contextlib
import time

_TRACING = [False]


@contextlib.contextmanager
def span(name: str):
    if not _TRACING[0]:
        yield
        return
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


class Window:
    def __init__(self, trace_dir=None):
        self.trace_dir = trace_dir
        self.t_begin = self.t_end = None
        self._span = None

    def begin(self):
        if self.trace_dir is not None:
            import jax

            jax.profiler.start_trace(self.trace_dir)
            _TRACING[0] = True
            self._span = span("bench.window")
            self._span.__enter__()
        self.t_begin = time.perf_counter()

    def end(self):
        if self.t_end is not None:
            return
        self.t_end = time.perf_counter()
        if self.trace_dir is not None:
            import jax

            self._span.__exit__(None, None, None)
            _TRACING[0] = False
            jax.profiler.stop_trace()
