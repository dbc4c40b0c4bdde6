"""Flagship step: share of device self time under `hetu_exit` (a looped
model's exit gate, its n_loops head passes, q and the entropy), mean over
chips; None where the program wrote no such scope (reduce/loop.py; traced
run only)."""
from benchmark.reduce import loop


def read(run):
    r = loop.for_run(run)
    return r["time_pct"] if r else None
