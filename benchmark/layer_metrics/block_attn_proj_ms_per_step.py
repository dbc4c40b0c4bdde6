"""Flagship step: device self time a traced step spends under `hetu_blk_qkv`
and `hetu_blk_wo`, every attention layer's: the fused q|k|v projection with
its bias, split, QK-norm, RoPE, multiplier and grouped-query repeat, and the
output projection with its bias; forward, recomputed and backward. THE
LIMIT: this is the scopes' time, not the matmuls'. On a decoder more than
half of `hetu_blk_qkv` is RoPE's rolls, QK-norm and the split (olmoe: 43.7
ms for a matmul of 4.35 ms a pass), and the pre-attention RMSNorm (the
sandwich norms too, on ouro) is fused into its fusions (chip runs, PR 34;
`python -m benchmark.reduce.block <trace dir>` splits qkv from wo). None
where the program wrote no such scope (reduce/block.py; traced run only)."""
from benchmark.reduce import block


def read(run):
    return block.scope_ms(run, block.QKV, block.WO)
