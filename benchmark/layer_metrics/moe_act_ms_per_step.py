"""Flagship step: device self time a traced step spends under
`hetu_moe_act`, every expert layer's: the relu^2 of UNGATED experts on the
rows held here, between the two grouped matmuls (a share's row loop, forward,
recomputed and backward), inside `hetu_moe_experts`; all phases. None where
the program wrote no such scope (reduce/nemotron_h.py; traced run only)."""
from benchmark.reduce import nemotron_h


def read(run):
    return nemotron_h.scope_ms(run, nemotron_h.ACT)
