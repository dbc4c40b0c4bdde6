"""Flagship step: share of device self time under the MoE block's four
scopes (`hetu_moe_route`, `_dispatch`, `_experts`, `_combine`: forward,
recomputed and backward ops alike), mean over chips; None where the
program wrote no such scope (reduce/moe.py; traced run only)."""
from benchmark.reduce import moe


def read(run):
    r = moe.for_run(run)
    return r["time_pct"] if r else None
