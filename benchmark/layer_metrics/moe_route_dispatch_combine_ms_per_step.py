"""Flagship step: device self time a traced step spends around the experts:
`hetu_moe_route` (router matmul, softmax, top-k, sort, group sizes, the two
auxiliary losses), `hetu_moe_dispatch` (token rows gathered by expert) and
`hetu_moe_combine` (un-permute, weight, sum over the picks), all phases.
None of it is matrix work: what it costs is the price of being sparse
(reduce/moe.py; traced run only)."""
from benchmark.reduce import moe


def read(run):
    return moe.scope_ms(run, "hetu_moe_route", "hetu_moe_dispatch",
                        "hetu_moe_combine")
