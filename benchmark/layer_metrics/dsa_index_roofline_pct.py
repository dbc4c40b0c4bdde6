"""Kernels: the index scores' two Mosaic kernels' share of their roofline
(`dsa_index_scores`, forward, for the selection and once more inside the
loss; `dsa_index_scores_bwd`): the passes counted in the trace (a pass is one
sequence of one layer, `row_blocks` calls) x the least time the chip could
take for one (the larger of the required FLOPs over the published peak FLOP/s
and the required bytes over the published peak bytes/s of this `device_kind`:
forward 2 * c * J a causal pair and its float32 score written once, backward
4 * c * J a KEPT pair) over their device time; a pass run again under `remat`
counted as run (reduce/dsa.py). None where the trace has neither kernel (the
XLA form off the chip, the parent of PR 44). Traced run only."""
from benchmark.reduce import dsa


def read(run):
    return dsa.share_of(run, dsa.index_roofline_pct, "kernels")
