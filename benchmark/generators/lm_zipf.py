"""Causal-LM pretraining batches: full sequences of zipf-distributed token
ids, the target of a position being the next token. Seeded; a fixed number
of batches that the adapter cycles.

Natural text is zipfian, and the skew matters to a mixture-of-experts
model: the router sees few distinct embeddings often and many rarely, so
expert loads are uneven and the groups of the grouped matmul differ in
size, which uniform ids would hide.

Traffic parameters: `sequences` (a step), `seq_len`, `zipf_exponent` (rank
r is drawn with probability ~ 1 / r^exponent over the whole vocabulary; the
rank -> id map is a seeded permutation), `batches`.
"""
import numpy as np


def generate(traffic, config, seed, sequences=None):
    """A list of `batches` batches {"tokens", "targets"}, each (n, seq_len)
    int32. `sequences` overrides n and gives one batch drawn from another
    stream of the same seed (the correctness sample: other sequences of
    the same language, so the same rank -> id map)."""
    vocab, T = int(config["vocab_size"]), int(traffic["seq_len"])
    n = int(sequences or traffic["sequences"])
    weights = 1.0 / np.arange(1, vocab + 1) ** float(traffic["zipf_exponent"])
    cdf = np.cumsum(weights / weights.sum())
    id_of_rank = np.random.default_rng([int(seed), 0x6c6d7a]).permutation(
        vocab).astype(np.int32)
    rng = np.random.default_rng([int(seed), 0x6c6d7a, sequences is not None])
    batches = []
    for _ in range(int(traffic["batches"]) if sequences is None else 1):
        ranks = np.minimum(np.searchsorted(cdf, rng.random((n, T + 1))),
                           vocab - 1)
        ids = id_of_rank[ranks]
        batches.append({"tokens": np.ascontiguousarray(ids[:, :-1]),
                        "targets": np.ascontiguousarray(ids[:, 1:])})
    return batches
