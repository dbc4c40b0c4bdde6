"""Flagship step: device self time a traced step spends under
`hetu_blk_attn`: scores, softmax and P V, so the flash kernels AND what the
compiler puts around them (the passes that set dq and dv into the fused
gradient), or the dot / ring path; forward, recomputed and backward. None
where the program wrote no such scope (reduce/block.py; traced run only)."""
from benchmark.reduce import block


def read(run):
    return block.scope_ms(run, block.ATTN)
