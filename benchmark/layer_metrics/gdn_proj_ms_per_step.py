"""Flagship step: device self time a traced step spends under
`hetu_gdn_proj`, every gdn layer's: W_qkvz (one matmul, 2,048 -> 12,288),
W_ba and W_o, forward, recomputed and backward; None where the program wrote
no such scope (reduce/gdn.py; traced run only)."""
from benchmark.reduce import gdn


def read(run):
    return gdn.scope_ms(run, gdn.PROJ)
