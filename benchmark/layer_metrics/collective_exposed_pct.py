"""Parallel layouts: the share of collective time during which no compute
operation runs on that chip (mean over chips): what overlap does not hide."""


def read(run):
    t = run["trace"]
    if not t:
        return None
    coll = sum(c["collective_s"] for c in t["chips"])
    if not coll:
        return None
    return 100.0 * sum(c["collective_exposed_s"] for c in t["chips"]) / coll
