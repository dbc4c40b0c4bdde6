"""Graph executor: median host time of `hetu.dispatch` a `hetu_step`: the
jitted call's argument assembly and `fn(*args)`: host dispatch time, not
device time (reduce/inside.py; traced run only)."""
from benchmark.reduce import inside


def read(run):
    return inside.host_value(run, "dispatch_ms_p50")
