"""Kernels: the full layers' flash calls' share of their roofline in a stack
that has window layers beside them: the calls under `hetu_blk_attn` counted
in the trace x (forward 4 * d * H * P, backward 10 * d * H * P a batch row, P
= T (T + 1) / 2 causal pairs, H the full layers' own head count;
reduce/swa.py; a forward run again under `remat` counted as run) over their
device time x the published peak of this `device_kind` (reduce/peaks.py).
None where the program wrote none of the window model's scopes. Traced run
only."""
from benchmark.reduce import swa


def read(run):
    return swa.roofline_of(run, swa.FULL)
