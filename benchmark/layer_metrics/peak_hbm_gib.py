"""Device: peak HBM in use on the fullest chip: the allocator's
`peak_bytes_in_use` (live buffers) plus its `peak_bytes_reserved` (the
running program's scratch), as harness/device.py says."""


def read(run):
    peak = run["device"].get("memory_peak_bytes")
    return peak / 2 ** 30 if peak else None
