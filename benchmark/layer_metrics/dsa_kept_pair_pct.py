"""Flagship step: the (query, key) pairs the selection keeps over the causal
pairs, all layers, in percent, from the program's own counter
(`transformer.dsa_stats` on the last step's batch; not a trace): sum_t min(t
+ 1, topk) of T (T + 1) / 2, 23.4 at 16,384 tokens and 2,048 keys, 100 while
topk >= T. The work attention REQUIRES follows it; what a dense kernel under
a mask computes does not. None where the program counts none."""
from benchmark.reduce import dsa


def read(run):
    return dsa.kept_pair_pct(run)
