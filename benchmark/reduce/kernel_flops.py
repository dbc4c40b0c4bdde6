"""Operations one call of a kernel requires, from its shapes alone: the
numerator of `<kernel>_roofline` shares. (ISSUE 23 asked for these in
reduce/flops.py; a PR may not edit a file the benchmark already has, so
they sit beside it.) What a kernel recomputes of its own accord is its
choice and not counted; a call the program makes twice (the forward again
under `remat`) is counted as run, because the kernel ran."""


def flash_fwd_flops(batch_heads, seq, head_dim):
    """Attention forward, every key counted (padded ones too; no causal
    skip is assumed): QK^T and PV, 2 * T*T*d multiply-adds each."""
    return 4.0 * batch_heads * seq * seq * head_dim


def flash_bwd_flops(batch_heads, seq, head_dim):
    """Attention backward: the scores again, dP, dV, dK and dQ: five
    T x T x d matmuls. The program's two kernels (dq; dk+dv) each rebuild
    the scores and dP, seven matmuls in all: the two extra are not
    required."""
    return 10.0 * batch_heads * seq * seq * head_dim
