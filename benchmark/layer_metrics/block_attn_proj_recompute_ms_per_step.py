"""Flagship step: the part of `block_attn_proj_ms_per_step` that is a
`jax.checkpoint`ed layer's forward pass run again in the backward pass
(`hetu_blk_qkv` and `hetu_blk_wo` under `rematted_computation`): what
keeping the projection, or not running `wo` again under a sandwich norm,
would save. None where the program wrote no such scope (reduce/block.py;
traced run only)."""
from benchmark.reduce import block


def read(run):
    return block.scope_ms(run, block.QKV, block.WO, phases=("recompute",))
