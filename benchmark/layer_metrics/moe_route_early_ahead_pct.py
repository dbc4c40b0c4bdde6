"""Flagship step: of the expert layers of the traced FORWARD passes, the
share whose routing ops (forward ops under `hetu_moe_route_early`) all END
before the layer's forward flash kernel STARTS, from the ops' own times in
the device trace: 100 = the compiler ran the routing ahead of attention, as
the program issued it; 0 = it sank the routing behind attention. None where
the program wrote no such scope (reduce/smallthinker.py; traced run
only)."""
from benchmark.reduce import smallthinker


def read(run):
    return smallthinker.ahead_pct(run)
