"""Flagship step: device self time a traced step spends under
`hetu_ssm_conv` and `hetu_ssm_gate`: the mixer's elementwise parts (the
causal depthwise convolution with bias and SiLU; y SiLU(z) and its RMSNorm),
bound by memory bandwidth; None where the program wrote no such scope
(reduce/ssm.py; traced run only)."""
from benchmark.reduce import ssm


def read(run):
    return ssm.scope_ms(run, ssm.CONV, ssm.GATE)
