"""Graph executor: share of the traced window (first `hetu_step`'s start
to the last one's end or the last device op's) in which the idlest chip
runs nothing while the host is inside a `hetu_step`: the device waiting on
`Executor.run` itself, which `device_idle_pct` cannot tell from idleness
under the job's own `sync` (reduce/inside.py; traced run only)."""
from benchmark.reduce import inside


def read(run):
    r = inside.for_run(run)
    return r["starved_pct"] if r else None
