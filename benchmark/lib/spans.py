"""An in-memory span recorder with the interface the program's tracer has
(``span``, ``instant``, ``counter``, ``complete``, ``flush``, ``close``), so
``ServingEngine(tracer=...)`` writes its schedule / prefill / decode /
sample / emit spans here, and the harness puts its own spans around the
calls it makes. Spans are kept in memory and read when the run ends.

While a profiler trace is being taken, every span is also entered as a
``jax.profiler.TraceAnnotation``, which puts it on the trace's own clock:
that is how an idle gap of the device is named by what the host was doing.
"""

from __future__ import annotations

import time
from typing import List, Optional, Tuple


class _Span:
    __slots__ = ("_rec", "_name", "_args", "_t0", "_ann")

    def __init__(self, rec: "SpanRecorder", name: str, args: Optional[dict]):
        self._rec, self._name, self._args, self._ann = rec, name, args, None

    def __enter__(self):
        if self._rec.annotate:
            import jax

            self._ann = jax.profiler.TraceAnnotation(
                self._rec.prefix + self._name)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._rec.spans.append((self._name, self._t0, t1, self._args))
        return False


class SpanRecorder:
    #: how the harness's and the engine's spans are told apart from the
    #: profiler's own host events in the trace
    prefix = "bench:"

    def __init__(self) -> None:
        self.spans: List[Tuple[str, float, float, Optional[dict]]] = []
        self.instants: List[Tuple[str, float, dict]] = []
        self.annotate = False
        self.path = None

    def span(self, name: str, **args) -> _Span:
        return _Span(self, name, args or None)

    def instant(self, name: str, **args) -> None:
        self.instants.append((name, time.perf_counter(), args))

    def complete(self, name: str, t0: float, t1: float, **args) -> None:
        self.spans.append((name, t0, t1, args or None))

    def counter(self, name: str, **values) -> None:
        pass

    def flush(self) -> None:
        pass

    def close(self) -> None:
        pass

    def durations_ms(self, name: str, t0: float, t1: float) -> List[float]:
        """Durations of the spans of that name that ended in [t0, t1]."""
        return [(b - a) * 1e3 for n, a, b, _ in list(self.spans)
                if n == name and t0 <= b <= t1]
