"""Entry points: programs lowered or compiled before the measured window (the
weights' init, the step, helpers, the Executor's second build)."""
from benchmark.reduce import startup


def read(run):
    return startup.value(run, "programs")
