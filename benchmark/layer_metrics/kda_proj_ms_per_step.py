"""Flagship step: device self time a traced step spends under
`hetu_kda_proj`, every kda layer's: W_q, W_k, W_v (one matmul), W_o and the
five small projections (the two low-rank gates' pairs and beta's), forward,
recomputed and backward; None where the program wrote no such scope
(reduce/kda.py; traced run only)."""
from benchmark.reduce import kda


def read(run):
    return kda.scope_ms(run, kda.PROJ)
