"""Flagship step: device self time a traced step spends running the forward
again in the backward pass (`rematted_computation` under a `transpose(`):
the program's own choice under `remat` (reduce/inside.py:phase_of), mean
over chips; None where nothing was recomputed. Traced run only."""
from benchmark.reduce import inside


def read(run):
    return inside.phase_ms(run, "recompute")
