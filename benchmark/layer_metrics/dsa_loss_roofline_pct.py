"""Kernels: the loss's target kernel's share of its roofline
(`dsa_head_probs`: the attention's probabilities rebuilt from q, k and the
flash kernels' row statistic, summed over the heads in VMEM): the passes
counted in the trace (one sequence of one layer, `row_blocks` calls) x the
least time the chip could take for one (2 * d * H FLOPs a KEPT pair and its
float32 sum written once, against the published peaks of this `device_kind`)
over its device time; a pass run again under `remat` counted as run. The
kernel computes every causal tile, so it reads at most the kept share times
its own efficiency (reduce/dsa.py). None where the trace has no such kernel.
Traced run only."""
from benchmark.reduce import dsa


def read(run):
    return dsa.share_of(run, dsa.loss_roofline_pct, "kernels")
