"""Entry points: seconds of jaxpr tracing and MLIR lowering before the measured
window, the union of the compile log's `trace` and `lower` spans less what a
compile covers: paid on every start, warm cache or cold."""
from benchmark.reduce import startup


def read(run):
    return startup.value(run, "trace_lower_s")
