"""Flagship step: share of device self time in learned sparse attention: the
four `hetu_dsa_*` scopes (the indexer's projections; its index scores; the
selection of the kept keys and its packed masks; the indexer's loss, target
and gradient) and the flash kernels that attend under the mask; forward,
recomputed and backward ops alike, mean over chips. The attention's own
projections stay with `hetu_blk_qkv` / `hetu_blk_wo`. None where the program
wrote no such scope (reduce/dsa.py; traced run only)."""
from benchmark.reduce import dsa


def read(run):
    r = dsa.for_run(run)
    return r["time_pct"] if r else None
