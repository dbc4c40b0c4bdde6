"""Flagship step: device self time a traced step spends under
`hetu_moe_shared`: the shared expert, one SwiGLU MLP on every token beside
the routed picks, every expert layer's; all phases. A fifth part of the
expert block, in none of `reduce/moe.py`'s four. None where the program
wrote no such scope (reduce/mla.py; traced run only)."""
from benchmark.reduce import mla


def read(run):
    return mla.scope_ms(run, mla.SHARED)
