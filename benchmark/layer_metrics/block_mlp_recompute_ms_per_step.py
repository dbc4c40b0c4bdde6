"""Flagship step: the part of `block_mlp_ms_per_step` that is a
`jax.checkpoint`ed layer's forward pass run again in the backward pass
(`hetu_blk_mlp_up` and `hetu_blk_mlp_down` under `rematted_computation`):
what keeping the activation's input, or not running `w2` again under a
sandwich norm, would save. None where the program wrote no such scope
(reduce/block.py; traced run only)."""
from benchmark.reduce import block


def read(run):
    return block.scope_ms(run, block.MLP_UP, block.MLP_DOWN, phases=("recompute",))
