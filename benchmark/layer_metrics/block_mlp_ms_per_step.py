"""Flagship step: device self time a traced step spends under
`hetu_blk_mlp_up` (w1, and w3; bias; GELU or SiLU x up) and
`hetu_blk_mlp_down` (w2 and its bias), every dense MLP's; forward,
recomputed and backward. A MoE block writes `hetu_moe_*` instead. None where
the program wrote no such scope (reduce/block.py; traced run only)."""
from benchmark.reduce import block


def read(run):
    return block.scope_ms(run, block.MLP_UP, block.MLP_DOWN)
