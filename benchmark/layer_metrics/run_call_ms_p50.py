"""Graph executor: median host time of one `Executor.run` call, from the
benchmark's own `run_call` span (traced run only)."""
import statistics


def read(run):
    d = run["spans"].durations("run_call")
    return 1e3 * statistics.median(d) if d else None
