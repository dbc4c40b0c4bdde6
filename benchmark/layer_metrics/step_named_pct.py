"""Flagship step: the share of a traced step's device self time that the
program can name: under any scope of its vocabulary other than `hetu_fwd`
alone (block, MoE, exit, SSM, `hetu_embed`, `hetu_head`, `hetu_opt`) or in a
collective. The remainder is residual adds, the scans' bookkeeping and what
the compiler put between the parts. None where the program wrote none of the
block scopes (reduce/block.py; traced run only)."""
from benchmark.reduce import block


def read(run):
    return block.named_pct(run)
