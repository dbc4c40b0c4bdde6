"""Flagship step / graph executor: device self time a traced step spends in
the backward pass: ops under a `transpose(` that are not a `checkpoint`'s
recomputed body (reduce/inside.py:phase_of), mean over chips; None where
the program wrote no phase scope. Traced run only."""
from benchmark.reduce import inside


def read(run):
    return inside.phase_ms(run, "bwd")
