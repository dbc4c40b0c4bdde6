"""PS tier: row pulls that blocked a step (no prefetched rows to take),
per step of the whole run (`ex.ps_runtime.perf`)."""


def read(run):
    ps = run["counters"].get("ps")
    steps = run["counters"].get("steps")
    if not ps or not steps:
        return None
    return ps["sync_pulls"] / steps
