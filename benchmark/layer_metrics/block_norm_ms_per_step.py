"""Flagship step: device self time a traced step spends under
`hetu_blk_norm`: every LayerNorm / RMSNorm of the residual stream (pre,
post, sandwich, final; QK-norm is `hetu_blk_qkv`'s); forward, recomputed and
backward. THE LIMIT: a fusion counts under its root's scope, and the compiler
fuses an RMSNorm into the matmul that reads it (`hetu_blk_qkv`,
`hetu_blk_mlp_up`, `hetu_ssm_proj`), so on the RMSNorm decoders this reads
only the residue that stays outside (1.1 ms on granite, 2.7 on ouro, 5.6 on
olmoe against 16.7 on BERT seq512, whose LayerNorms stay apart, less ~10 ms
inside the MLP's and `wo`'s fusions; chip runs, PR 34): a change to the
norms there moves the projections' and the MLP's metrics, not this one. None
where the program wrote no such scope (reduce/block.py; traced run only)."""
from benchmark.reduce import block


def read(run):
    return block.scope_ms(run, block.NORM)
