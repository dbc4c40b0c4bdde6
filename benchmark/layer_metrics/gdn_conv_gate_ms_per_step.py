"""Flagship step: device self time a traced step spends under
`hetu_gdn_conv` and `hetu_gdn_gate`, every gdn layer's: the one 4-tap causal
depthwise convolution over [q | k | v] with its SiLU; the log-decay a head,
beta, q's and k's L2 norms, the key heads' repeat, and the output's RMSNorm a
head with SiLU(z): the mixer's elementwise work around the scan, forward,
recomputed and backward; None where the program wrote no such scope
(reduce/gdn.py; traced run only)."""
from benchmark.reduce import gdn


def read(run):
    return gdn.scope_ms(run, gdn.CONV, gdn.GATE)
