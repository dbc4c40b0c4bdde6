"""The benchmark's own spans around its calls into the program. Off in an
end-to-end run (a shared no-op context); in a traced run each span is kept
in memory and also written into the profiler's trace as a
`jax.profiler.TraceAnnotation`, so host spans and device ops share a clock."""
import contextlib
import time

_NULL = contextlib.nullcontext()


class Spans:
    def __init__(self, enabled):
        self.enabled = bool(enabled)
        self.records = {}      # name -> [(start_s, end_s), ...] perf_counter

    def __call__(self, name):
        return self._span(name) if self.enabled else _NULL

    @contextlib.contextmanager
    def _span(self, name):
        from jax.profiler import TraceAnnotation
        with TraceAnnotation(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.records.setdefault(name, []).append(
                    (t0, time.perf_counter()))

    def durations(self, name):
        return [b - a for a, b in self.records.get(name, ())]
