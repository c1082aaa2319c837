"""The tracer handed to ``Server.serve(tracer=...)`` in the traced run.

It has ``SpanTracer``'s interface (``span(name, **attrs)`` -> a context
manager with ``.set``) and, while the profile is on, opens a
``jax.profiler.TraceAnnotation`` for each span, so the program's own host
spans land in the profiler's trace on the device's clock. It keeps each
span's host-clock duration. Given ``seconds`` and ``stop``, it calls
``stop`` (which ends the profile) at the first round that begins that
long after it was made, so the profile holds a slice of whole rounds;
the time that takes is kept as a span ``profile_stop``.
"""
from __future__ import annotations

import time

import jax


class _Span:
    __slots__ = ("_tracer", "name", "attrs", "_ann", "_t0")

    def __init__(self, tracer, name, attrs):
        self._tracer, self.name, self.attrs = tracer, name, attrs

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def __enter__(self):
        self._ann = (jax.profiler.TraceAnnotation(self.name)
                     if self._tracer.profiling else None)
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._tracer.spans.append((self.name, self._t0, t1, self.attrs))
        return False


class Tracer:
    enabled = True

    def __init__(self, seconds: float | None = None, stop=None):
        #: (name, start s, end s, attrs) by the host's perf_counter
        self.spans: list = []
        self.profiling = True
        self._stop = stop
        self._until = None if seconds is None else time.perf_counter() + seconds

    def span(self, name: str, **attrs) -> _Span:
        if (name == "admit" and self._until is not None
                and time.perf_counter() >= self._until):
            self.finish()
        return _Span(self, name, attrs)

    def finish(self) -> None:
        """End the profile (once)."""
        if self.profiling:
            self.profiling = False
            if self._stop is not None:
                t0 = time.perf_counter()
                self._stop()
                self.spans.append(("profile_stop", t0, time.perf_counter(), {}))
