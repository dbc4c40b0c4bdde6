"""Flagship step: device self time a traced step spends under
`hetu_ssm_gate_norm`, every mamba layer's: the gated norm BY GROUP (a
statistic a group of d_inner / n_groups channels of y silu(z), and the
scaling), inside `hetu_ssm_gate`; all phases. None where the program wrote no
such scope: a norm over all channels opens none (reduce/nemotron_h.py; traced
run only)."""
from benchmark.reduce import nemotron_h


def read(run):
    return nemotron_h.scope_ms(run, nemotron_h.GATE_NORM)
