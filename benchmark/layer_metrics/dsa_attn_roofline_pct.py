"""Kernels: the flash attention kernels' share of the chip's peak FLOP/s by
the work the KEPT pairs require: the calls counted in the trace x (forward 4
* d * H * kept, backward 10 * d * H * kept a batch row; reduce/dsa.py; a
forward run again under `remat` counted as run) over their device time x the
published peak of this `device_kind` (reduce/peaks.py). A dense kernel under
a mask computes every causal pair and reads at most the kept share times its
own efficiency: the headroom of a kernel that skips the dropped pairs.
Compute-bound. Traced run only."""
from benchmark.reduce import dsa


def read(run):
    return dsa.share_of(run, dsa.attn_roofline_pct, "flash")
