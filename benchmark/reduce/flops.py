"""Operations and bytes a step requires, from its shapes alone. Forward
plus backward is three times the forward matmuls; recomputation under
`remat` is not counted (it is the program's choice, not the model's need)."""


def bert_pretrain_flops_per_token(hidden, layers, intermediate, vocab,
                                  seq, predictions):
    """Training FLOPs per input position of BERT pretraining (MLM + NSP),
    attention-inclusive, the MLM head over the predicted slots only.

    per layer, per token, forward: qkv 2*D*3D, out 2*D*D, MLP 2*2*D*F,
    attention scores and values 2*2*T*D (bidirectional: every key).
    MLM head per predicted slot: transform 2*D*D, tied decode 2*D*V.
    Pooler and NSP head per sequence: 2*D*D + 2*D*2.
    """
    D, F, T = hidden, intermediate, seq
    layer = 2 * D * 3 * D + 2 * D * D + 4 * D * F + 4 * T * D
    head = (2 * D * D + 2 * D * vocab) * predictions / T
    pool = (2 * D * D + 4 * D) / T
    return 3.0 * (layers * layer + head + pool)
