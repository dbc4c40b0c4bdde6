"""Flagship step: share of device self time in latent attention: the three
`hetu_mla_*` scopes (Wq and q's rotation; Wkv_a, the latent's norm and the
one rotary key's rotation; Wkv_b and the assembly of k) and the flash kernels
at their two widths; forward, recomputed and backward ops alike, mean over
chips. `wo` stays with `hetu_blk_wo`. None where the program wrote no such
scope (reduce/mla.py; traced run only)."""
from benchmark.reduce import mla


def read(run):
    r = mla.for_run(run)
    return r["time_pct"] if r else None
