"""Kernels: the (query, key) pairs the window layers' forward kernels
COMPUTE over the pairs they keep, in percent, from the program's own counter
(the adapter's check MEASURES them on the device through the first window
layer's own mixer, `transformer.attention_visits`: a chunk of keys made NaN at
a time, the rows that come out NaN counted; not a trace): 100 = no waste;
whole 512 x 512 tiles make ~200 the floor at a window of 512; a kernel that
walks every causal tile under the mask reads 1,676 at 16,384 tokens. None
where the program counts none."""
from benchmark.reduce import swa


def read(run):
    return swa.computed_pair_pct(run)
