"""BERT pretraining batches (MLM + NSP) in the data pipeline's row format
(`bert.batch_from_instances`): seeded, a fixed number of batches that the
adapter cycles.

Traffic parameters: `sequences` (a step, all chips together), `seq_len`,
`predictions` (MLM slots a sequence), `short_seq_prob` (Devlin et al.: that
share of sequences is cut to a random length and padded; `input_mask`
carries it), `mask_share` (slots filled: 15 % of the real tokens, at most
`predictions`), `batches`.
"""
import numpy as np


def _batch(rng, vocab, n, T, P, short_prob, mask_share):
    lengths = np.where(rng.random(n) < short_prob,
                       rng.integers(2, T + 1, n), T)
    pos_in_seq = np.arange(T)[None, :]
    mask = (pos_in_seq < lengths[:, None]).astype(np.int32)
    # two segments: sentence A then sentence B, split inside the real part
    split = (lengths * rng.uniform(0.25, 0.75, n)).astype(np.int64)
    positions = np.zeros((n, P), np.int32)
    weights = np.zeros((n, P), np.float32)
    for i, length in enumerate(lengths):
        k = int(min(P, max(1, round(mask_share * length)), length - 1))
        # position 0 is [CLS], never masked: a 0 marks a padded slot
        positions[i, :k] = np.sort(
            rng.choice(np.arange(1, length), size=k, replace=False))
        weights[i, :k] = 1.0
    return {
        "input_ids": (rng.integers(0, vocab, (n, T)) * mask).astype(np.int32),
        "input_mask": mask,
        "segment_ids": ((pos_in_seq >= split[:, None]) * mask).astype(np.int32),
        "mlm_positions": positions,
        "mlm_ids": rng.integers(0, vocab, (n, P)).astype(np.int32),
        "mlm_weights": weights,
        "nsp_label": rng.integers(0, 2, (n,)).astype(np.int32),
    }


def generate(traffic, config, seed, sequences=None):
    """A list of `batches` batches; `sequences` overrides the batch size
    (the correctness sample)."""
    rng = np.random.default_rng([int(seed), 0x6d6c6d])
    n = int(sequences or traffic["sequences"])
    return [_batch(rng, config["vocab_size"], n, traffic["seq_len"],
                   traffic["predictions"], traffic["short_seq_prob"],
                   traffic["mask_share"])
            for _ in range(int(traffic["batches"]) if sequences is None
                           else 1)]
