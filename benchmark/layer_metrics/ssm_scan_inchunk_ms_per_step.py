"""Flagship step: device self time a traced step spends under
`hetu_ssd_inchunk`, inside `hetu_ssm_scan`, every mamba layer's: the chunks
cut, the cumulative log-decay, the decay matrix, the masked C B^T and its
product with x dt (what a fused SSD kernel would replace first); forward,
recomputed and backward. None where the program wrote no such scope
(reduce/block.py; traced run only)."""
from benchmark.reduce import block


def read(run):
    return block.scope_ms(run, block.SSD_INCHUNK)
