"""Kernels: the flash attention kernels' share of the chip's peak FLOP/s:
operations the calls counted in the trace required (reduce/kernel_flops.py:
forward 4*B*H*T*T*d, backward 10*B*H*T*T*d, padded keys counted, a
recomputed forward counted as run) over their device time x the published
peak of this `device_kind` (reduce/peaks.py). Compute-bound at head size
64: the FLOP bound is the larger one. Traced run only."""
from benchmark.reduce import inside, peaks


def read(run):
    r = inside.for_run(run)
    if not r or not r["flash"] or not r["flash"]["seconds"]:
        return None
    f = r["flash"]
    peak = peaks.peaks(run["device"]["kind"])["tflops"] * 1e12
    return 100.0 * f["flops"] / f["seconds"] / peak
