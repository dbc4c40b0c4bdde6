"""Kernels: share of device busy time inside Mosaic (Pallas) custom calls,
from the `XLA Ops` line of the device trace (mean over chips)."""


def read(run):
    t = run["trace"]
    if not t:
        return None
    busy = sum(c["ops_self_s"] for c in t["chips"])
    return 100.0 * sum(c["mosaic_s"] for c in t["chips"]) / busy if busy \
        else None
