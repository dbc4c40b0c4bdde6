"""Flagship step: device self time a traced step spends under `hetu_exit`,
a looped model's exit head: the gate, the n_loops passes of the vocabulary
head (fused cross-entropy forward, dh and dW), the exit distribution and
its entropy, forward and backward ops alike; None where the program wrote
no such scope (reduce/loop.py; traced run only)."""
from benchmark.reduce import loop


def read(run):
    r = loop.for_run(run)
    return r["exit_total_ms_per_step"] if r else None
