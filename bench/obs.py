"""What the benchmark hands the program to watch it: a tracer that puts every
span of the program's ``repro.obs`` tracer onto the profiler's clock, a
serving clock, and a counter of compilations.

``ProfilerTracer`` has the tracer interface the runtime and the engine call
(``begin``/``span``/``counter``) and opens a
``jax.profiler.TraceAnnotation`` named ``span:<name>`` for each span, so the
device trace shows what the host was doing in every gap.  It keeps nothing.
"""

from __future__ import annotations

import time

import jax
from jax import monitoring

SPAN_PREFIX = "span:"


class _Annotation:
    __slots__ = ("_ta",)

    def __init__(self, name: str):
        self._ta = jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
        self._ta.__enter__()

    def set(self, key, value) -> None:
        pass

    def end(self) -> None:
        if self._ta is not None:
            self._ta.__exit__(None, None, None)
            self._ta = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.end()
        return False


class ProfilerTracer:
    def begin(self, name: str, track: str = "main", args=None) -> _Annotation:
        return _Annotation(name)

    span = begin

    def counter(self, name: str, value, track: str = "counters") -> None:
        pass


def annotate(name: str):
    """A harness span on the profiler's clock."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + "bench." + name)


class BenchClock:
    """The serving clock the runtime runs on (same interface as the
    program's ``WallClock``): seconds since the window opened.  ``t0`` is the
    host time the window opened at."""

    def __init__(self):
        self.t0 = time.perf_counter()
        self.idle_s = 0.0  # slept waiting for the next arrival

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def reset(self) -> None:
        self.t0 = time.perf_counter()
        self.idle_s = 0.0

    def on_round(self, depth=None) -> None:
        pass

    def wait_until(self, t: float) -> None:
        d = t - self.now()
        if d > 0:
            with annotate("wait_arrival"):
                time.sleep(d)
            self.idle_s += d


class CompileCounter:
    """Counts programs traced and compiled (or loaded from the persistent
    cache) while ``armed``.  A warm window counts none."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.armed = False
        self.traces = 0
        self.compiles = 0
        self.names: list[str] = []
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if not self.armed:
            return
        if event == self.TRACE:
            self.traces += 1
        elif event == self.COMPILE:
            self.compiles += 1
            self.names.append(str(kw.get("fun_name", "?")))

    def close(self) -> None:
        monitoring.unregister_event_duration_listener(self._on_event)
