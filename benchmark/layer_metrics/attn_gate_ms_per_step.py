"""Flagship step: device self time a traced step spends under
`hetu_attn_gate`, every layer's: the per-head gate on attention's output (Wg,
the sigmoid, the product with o); all phases. None where the program wrote
no such scope (reduce/swa.py; traced run only)."""
from benchmark.reduce import swa


def read(run):
    return swa.scope_ms(run, swa.GATE)
