"""Entry points: programs compiled before the measured window that asked the
persistent cache and missed: whether this run's `setup_s` was a warm one."""
from benchmark.reduce import startup


def read(run):
    return startup.value(run, "cache_miss_programs")
