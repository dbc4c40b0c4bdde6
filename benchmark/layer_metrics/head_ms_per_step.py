"""Flagship step: device self time a traced step spends under `hetu_head`:
the vocabulary head and its loss, fused kernels or einsum (a looped model's
n_loops passes, under `hetu_exit`; BERT's MLM transform, tied decoder and
NSP head); forward and backward. None where the program wrote no such scope
(reduce/block.py; traced run only)."""
from benchmark.reduce import block


def read(run):
    return block.scope_ms(run, block.HEAD)
