"""Device: 1 - union of the `XLA Ops` intervals over the traced window, on
the idlest chip. `Steps` and `XLA Modules` events are not busy time."""


def read(run):
    t = run["trace"]
    return max(c["idle_pct"] for c in t["chips"]) if t else None
