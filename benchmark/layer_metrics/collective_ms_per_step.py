"""Parallel layouts: time a chip spends in collective operations a step
(union of their intervals in the device trace, mean over chips)."""


def read(run):
    t = run["trace"]
    if not t or not t.get("steps"):
        return None
    coll = [c["collective_s"] for c in t["chips"]]
    if not any(coll):
        return None
    return 1e3 * sum(coll) / len(coll) / t["steps"]
