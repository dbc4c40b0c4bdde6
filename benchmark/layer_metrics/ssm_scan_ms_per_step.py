"""Flagship step: device self time a traced step spends under
`hetu_ssm_scan`, every mamba layer's: dt, the cumulative log-decay, the
decay matrix, the chunked recurrence's four parts and the D skip, forward,
recomputed and backward; None where the program wrote no such scope
(reduce/ssm.py; traced run only)."""
from benchmark.reduce import ssm


def read(run):
    return ssm.scope_ms(run, ssm.SCAN)
