"""Flagship step: share of device self time in the Gated DeltaNet mixers: the
`hetu_gdn_*` scopes (projections, the convolution, gates and norms, the
chunked gated delta rule with its triangular system), every gdn layer's;
forward, recomputed and backward ops alike, mean over chips. None where the
program wrote no such scope (reduce/gdn.py; traced run only)."""
from benchmark.reduce import gdn


def read(run):
    return gdn.time_pct(run)
