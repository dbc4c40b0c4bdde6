"""Flagship step: device self time a traced step spends on the indexer's
scores, every layer's: `hetu_dsa_index_proj` (Wq_idx, Wk_idx and its
LayerNorm, Ww_idx, RoPE) and `hetu_dsa_index_scores` (I = sum_j w_j ReLU(qI_j
. kI) over the causal pairs, for the selection and once more inside the
loss, and its backward); all phases. None where the program wrote no such
scope (reduce/dsa.py; traced run only)."""
from benchmark.reduce import dsa


def read(run):
    return dsa.scope_ms(run, dsa.PROJ, dsa.SCORES)
