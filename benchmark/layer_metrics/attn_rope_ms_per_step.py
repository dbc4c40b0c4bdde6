"""Flagship step: device self time a traced step spends under
`hetu_attn_rope`, every layer's: the rotation of q and k, a window layer's
on all of a head's columns and a full layer's on half of them by YaRN's
table; all phases. The scope opens only in a model with window layers. None
where the program wrote no such scope (reduce/swa.py; traced run only)."""
from benchmark.reduce import swa


def read(run):
    return swa.scope_ms(run, swa.ROPE)
