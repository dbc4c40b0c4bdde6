"""Flagship step: device self time a traced step spends under
`hetu_dsa_select`, every layer's: the k-th largest index score of a query row
(16 counting passes over a block of rows), the ties, the kept set packed one
bit a pair both ways for the flash kernels; all phases (it has no backward:
forward and, under `remat`, forward again). None where the program wrote no
such scope (reduce/dsa.py; traced run only)."""
from benchmark.reduce import dsa


def read(run):
    return dsa.scope_ms(run, dsa.SELECT)
