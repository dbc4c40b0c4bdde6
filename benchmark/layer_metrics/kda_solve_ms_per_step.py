"""Flagship step: device self time a traced step spends under
`hetu_kda_solve`, every kda layer's: the inverse of a chunk's unit
lower-triangular system I + A (forward substitution on 16 x 16 diagonal
blocks, then the blocks below them) and its product with [V | K], forward,
recomputed and backward; a part of `kda_scan_ms_per_step`. None where the
program wrote no such scope (reduce/kda.py; traced run only)."""
from benchmark.reduce import kda


def read(run):
    return kda.scope_ms(run, kda.SOLVE)
