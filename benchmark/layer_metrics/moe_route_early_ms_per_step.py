"""Flagship step: device self time a traced step spends under
`hetu_moe_route_early`, every expert layer's: the routing of a layer whose
router reads the layer's INPUT (`Router.input` "block": the router's matmul,
the softmax, the top k, the counts, the sort and a share's plan, made before
the mixer in program order; `hetu_moe_route` lies inside it, so
`moe_route_dispatch_combine_ms_per_step` counts it too); all phases. None
where the program wrote no such scope (reduce/smallthinker.py; traced run
only)."""
from benchmark.reduce import smallthinker


def read(run):
    return smallthinker.early_ms(run)
