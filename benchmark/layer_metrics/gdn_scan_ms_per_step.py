"""Flagship step: device self time a traced step spends under
`hetu_gdn_scan`, every gdn layer's, whichever form of the rule runs (the
Mosaic kernels' `hetu_kda_scan` and the XLA form's `hetu_kda_solve` sit
INSIDE it): the cumulated decay, the products of positions, the triangular
system and its solution, the recurrence over the chunks' states and the
output, forward, recomputed and backward; None where the program wrote no
such scope (reduce/gdn.py; traced run only)."""
from benchmark.reduce import gdn


def read(run):
    return gdn.scope_ms(run, gdn.SCAN)
