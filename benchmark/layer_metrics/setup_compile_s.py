"""Entry points: seconds of XLA compiling before the measured window, the union
of the `backend` spans of programs that missed the persistent cache or did
not ask it: 0 on a warm start."""
from benchmark.reduce import startup


def read(run):
    return startup.value(run, "compile_s")
