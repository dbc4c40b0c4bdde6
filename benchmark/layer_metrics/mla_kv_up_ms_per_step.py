"""Flagship step: device self time a traced step spends under
`hetu_mla_kv_up`: Wkv_b (the 512-wide latent up to 32 x (128 + 128) columns)
and the assembly of k, every head's k_nope beside the token's one rotary
key: the price of keys that the kernels read as whole heads; all phases.
None where the program wrote no such scope (reduce/mla.py; traced run
only)."""
from benchmark.reduce import mla


def read(run):
    return mla.scope_ms(run, mla.KV_UP)
