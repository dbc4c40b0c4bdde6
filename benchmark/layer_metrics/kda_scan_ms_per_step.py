"""Flagship step: device self time a traced step spends under
`hetu_kda_scan`, every kda layer's, the triangular system (`hetu_kda_solve`,
inside it) INCLUDED: the cumulated decay, the pairwise products, the system
and its solution, the recurrence over the chunks' states and the output,
forward, recomputed (by the layer's checkpoint and by the segment's) and
backward; None where the program wrote no such scope (reduce/kda.py; traced
run only)."""
from benchmark.reduce import kda


def read(run):
    return kda.scope_ms(run, kda.SCAN, kda.SOLVE)
