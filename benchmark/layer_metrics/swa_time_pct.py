"""Flagship step: share of device self time in the window layers' attention
core: every op under `hetu_swa_attn` (the flash calls with a window; forward,
recomputed and backward alike), mean over chips. A full layer's core stays
under `hetu_blk_attn`. None where the program wrote no such scope
(reduce/swa.py; traced run only)."""
from benchmark.reduce import swa


def read(run):
    r = swa.for_run(run)
    return r["time_pct"] if r else None
