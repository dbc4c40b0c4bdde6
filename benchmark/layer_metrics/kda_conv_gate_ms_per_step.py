"""Flagship step: device self time a traced step spends under
`hetu_kda_conv` and `hetu_kda_gate`, every kda layer's: the three 4-tap
causal depthwise convolutions with their SiLU; the log-decay a channel, beta,
q's and k's L2 norms, and the output's RMSNorm a head with its sigmoid gate:
the mixer's elementwise work around the scan, forward, recomputed and
backward; None where the program wrote no such scope (reduce/kda.py; traced
run only)."""
from benchmark.reduce import kda


def read(run):
    return kda.scope_ms(run, kda.CONV, kda.GATE)
