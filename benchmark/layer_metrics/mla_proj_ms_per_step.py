"""Flagship step: device self time a traced step spends under the three
`hetu_mla_*` scopes, every layer's: latent attention's q projection, its
down projection to the latent and the rotary key, and its up projection to
every head's k_nope and v with the assembly of k; all phases. None where the
program wrote no such scope (reduce/mla.py; traced run only)."""
from benchmark.reduce import mla


def read(run):
    return mla.scope_ms(run, *mla.MLA)
