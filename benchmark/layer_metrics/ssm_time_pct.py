"""Flagship step: share of device self time under the four `hetu_ssm_*`
scopes (a Mamba-2 mixer's projections, convolution, chunked scan and gate,
forward, recomputed and backward ops alike), mean over chips; None where the
program wrote no such scope (reduce/ssm.py; traced run only)."""
from benchmark.reduce import ssm


def read(run):
    r = ssm.for_run(run)
    return r["time_pct"] if r else None
