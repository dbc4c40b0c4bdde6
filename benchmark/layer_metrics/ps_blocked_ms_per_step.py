"""PS tier: host time a `hetu_step` spends blocked on the parameter server:
`hetu.ps_pull` (staged lookups, prefetch misses, `wait_dense`) +
`hetu.ps_push` (gradient push issue, next-batch prefetch pulls), mean over
the traced steps; None for a job without a PS (reduce/inside.py; traced
run only). No manifest entry until a PS cell exists (PERF.md section 7)."""
from benchmark.reduce import inside


def read(run):
    if not run["counters"].get("ps"):
        return None
    return inside.host_value(run, "ps_blocked_ms_per_step")
