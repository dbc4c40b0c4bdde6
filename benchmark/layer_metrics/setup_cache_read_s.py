"""Entry points: seconds spent reading executables from the persistent cache
before the measured window (retrieval, deserialisation, load): the union of
the `backend` spans of programs that hit."""
from benchmark.reduce import startup


def read(run):
    return startup.value(run, "cache_read_s")
