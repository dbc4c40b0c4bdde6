"""Kernels: share of device self time inside the flash attention kernels
(Mosaic families whose name contains `flash_`: forward, the forward again
under `remat`, dq, dk+dv), mean over chips; None where no such kernel ran
(reduce/inside.py; traced run only)."""
from benchmark.reduce import inside


def read(run):
    r = inside.for_run(run)
    return r["flash"]["time_pct"] if r and r["flash"] else None
