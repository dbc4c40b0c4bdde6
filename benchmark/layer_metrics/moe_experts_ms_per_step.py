"""Flagship step: device self time a traced step spends under
`hetu_moe_experts`: the grouped matmuls (gate, up, down; forward, again
under `remat`, and their backward products) and the activation between
them (reduce/moe.py; traced run only)."""
from benchmark.reduce import moe


def read(run):
    return moe.scope_ms(run, "hetu_moe_experts")
