"""PS tier: share of steps whose embedding rows were already pulled by the
prefetch stream (`ex.ps_runtime.perf`, read after the window)."""


def read(run):
    ps = run["counters"].get("ps")
    if not ps:
        return None
    taken = ps["prefetch_hits"] + ps["prefetch_misses"] + ps["sync_pulls"]
    return 100.0 * ps["prefetch_hits"] / taken if taken else None
