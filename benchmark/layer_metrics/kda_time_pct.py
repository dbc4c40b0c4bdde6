"""Flagship step: share of device self time in Kimi Delta Attention: the
five `hetu_kda_*` scopes (projections, convolutions, gates and norms, the
chunked gated delta rule with its triangular system), every kda layer's;
forward, recomputed and backward ops alike, mean over chips. None where the
program wrote no such scope (reduce/kda.py; traced run only)."""
from benchmark.reduce import kda


def read(run):
    return kda.time_pct(run)
