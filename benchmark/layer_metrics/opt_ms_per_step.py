"""Flagship step / graph executor: device self time a traced step spends in
the optimizer update: ops under the `hetu_opt` scope and the copies the
compiler made for them (reduce/inside.py:phase_of), mean over chips; None
where the program wrote no phase scope. Traced run only."""
from benchmark.reduce import inside


def read(run):
    return inside.phase_ms(run, "opt")
