"""Flagship step: device self time a traced step spends under
`hetu_dsa_loss`, every layer's: the attention's head-summed probabilities
rebuilt from q, k and the kernels' row statistic, the KL against softmax(I)
over the kept keys, and its gradient on I (the index scores it runs again
are `dsa_index_ms_per_step`'s); all phases. None where the program wrote no
such scope (reduce/dsa.py; traced run only)."""
from benchmark.reduce import dsa


def read(run):
    return dsa.scope_ms(run, dsa.LOSS)
