"""Flagship step / graph executor: device self time a traced step spends in
the forward pass: every op with an `op_name` path that is under neither
`hetu_opt` nor a `transpose(` (reduce/inside.py:phase_of), mean over chips;
None where the program wrote no phase scope. Traced run only."""
from benchmark.reduce import inside


def read(run):
    return inside.phase_ms(run, "fwd")
