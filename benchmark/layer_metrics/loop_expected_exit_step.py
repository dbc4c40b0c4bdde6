"""Flagship step: the expected exit step sum_t t * q(t) of a looped model,
exits counted from 1, the mean over the tokens of the correctness sample,
from the program's own `transformer.exit_stats` after the window (1 = the
gate stops everything at the first pass, n_loops = it lets everything
through); None for a model with one exit."""


def read(run):
    stats = run["counters"].get("loop")
    return stats["expected_exit_step"] if stats else None
