"""Kernels: the window layers' flash calls' share of their roofline by the
work the KEPT pairs require: the calls under `hetu_swa_attn` counted in the
trace x (forward 4 * d * H * kept, backward 10 * d * H * kept a batch row,
kept = sum over t of min(t + 1, window); reduce/swa.py; a forward run again
under `remat` counted as run) over their device time x the published peak of
this `device_kind` (reduce/peaks.py). Kernels that compute whole tiles read
at most kept / computed (`swa_computed_pair_pct`) times their own
efficiency; a dense kernel under a mask a sixteenth of that at 16,384 tokens
and a window of 512. Traced run only."""
from benchmark.reduce import swa


def read(run):
    return swa.roofline_of(run, swa.WINDOW)
