"""Graph executor: median host time a `hetu_step` spends after the
dispatch: `hetu.prefetch` (the next batch's device_put) + `hetu.poststep`
(state commit, the anomaly guard's host read, hetuscope, telemetry)
(reduce/inside.py; traced run only)."""
from benchmark.reduce import inside


def read(run):
    return inside.host_value(run, "poststep_ms_p50")
