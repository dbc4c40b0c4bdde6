"""A Gated DeltaNet mixer in a trace and on paper: device self time under the
scopes the kind adds to the program's vocabulary (`hetu_gdn_proj`, W_qkvz,
W_ba and W_o; `hetu_gdn_conv`, the one causal convolution and SiLU;
`hetu_gdn_gate`, the log-decay a head, beta, the L2 norms, the key heads'
repeat and the head norm with SiLU(z); `hetu_gdn_scan`, the chunked gated
delta rule, whichever form runs: written in `transformer._gdn`), by step
phase; the scan's required operations and bytes
FROM ITS SHAPES with a decay a head, whatever implements it; and the
operations a token of Qwen3-Next requires, by layer.

An op's scope here is the INNERMOST segment of its `op_name` path that is one
of these (`reduce/scopes.py`, the one reader of a tuple of scopes). The rule
runs in `models/kda.py` under the mixer's scope: the Mosaic kernels' ops sit
at `.../hetu_gdn_scan/hetu_kda_scan/kda_fwd/...` in all three phases and the
XLA form's system at `.../hetu_gdn_scan/hetu_kda_solve/...`; neither inner
name is this file's, so both count as the scan's. A program that lacks the
scopes (any other model; the parent of the PR that added them) reads as
"nothing": every function returns None and does not raise.

`python -m benchmark.reduce.gdn <trace dir>` prints the table.
"""
import functools
import sys

from . import peaks, scopes

# a copy of the program's vocabulary, as in inside.py
PROJ, CONV, GATE, SCAN = SCOPES = (
    "hetu_gdn_proj", "hetu_gdn_conv", "hetu_gdn_gate", "hetu_gdn_scan")
PHASES = scopes.PHASES


# -- on paper -------------------------------------------------------------------

def scan_required_flops(batch, seq, key_heads, value_heads, key_dim,
                        value_dim, chunk):
    """Training FLOPs of one gated-delta-rule scan with a decay a HEAD,
    forward plus backward = 3 x forward; recomputation not counted. Forward,
    with C = chunk and seq / C chunks, 2 a multiply-add:

    a KEY head: the two products of positions (k.k for the system, q.k for
    the output) at the causal half they require, C (C + 1) / 2 pairs, 2 *
    key_dim each; its value heads share them (the decay that tells the value
    heads apart multiplies a pair once, elementwise: not counted);
    a VALUE head: the triangular system by forward substitution on its C (C
    - 1) / 2 entries, against value_dim + key_dim right-hand columns ([V |
    K]); the state's three products a position: W S (key_dim x value_dim),
    q S and the update k u^T (the same each); the output's in-chunk product
    P U at its causal half, 2 * value_dim a pair.
    The elementwise work (the decay matrix's exponentials, the norms, the
    cumulated sum) is not counted: it runs on the VPU, not the MXU."""
    pairs, below = chunk * (chunk + 1) // 2, chunk * (chunk - 1) // 2
    a_chunk = (key_heads * 2 * pairs * 2.0 * key_dim
               + value_heads * (below * 2.0 * (value_dim + key_dim)
                                + pairs * 2.0 * value_dim))
    a_position = value_heads * 3 * 2.0 * key_dim * value_dim
    return 3.0 * batch * ((seq // chunk) * a_chunk + seq * a_position)


def scan_required_bytes(batch, seq, key_heads, value_heads, key_dim,
                        value_dim, itemsize=2):
    """Bytes one scan must move, forward plus backward = 3 x forward (the
    backward pass reads the forward's operands and the output's cotangent and
    writes every operand's): forward reads q and k (key_dim a KEY head and
    position) and v (value_dim a value head) at the compute dtype, the
    log-decay and beta (float32, ONE each a value head), and writes o like v.
    The chunk moves nothing: the decay matrix, the system, its solution and
    the carried state are made and used on the chip."""
    a_position = (itemsize * (2 * key_heads * key_dim
                              + 2 * value_heads * value_dim)
                  + (4 + 4) * value_heads)
    return 3.0 * batch * seq * a_position


def mixers_of(config):
    """"gdn" or "attention" a layer, from 0."""
    every = config["full_attention_interval"]
    return ["attention" if (i + 1) % every == 0 else "gdn"
            for i in range(config["num_hidden_layers"])]


def _scan_sizes(config):
    return (config["linear_num_key_heads"], config["linear_num_value_heads"],
            config["linear_key_head_dim"], config["linear_value_head_dim"])


def forward_flops_by_part(config, seq, chunk=64):
    """Forward FLOPs a TOKEN of ONE layer's part, and of the head, from
    config.json and the sequence length; the matmuls' 2 a multiply-add,
    elementwise work not counted:

    gdn: W_qkvz 2 D (2 Hk K + 2 Hv V), W_ba 2 D 2 Hv, the `taps`-tap
    convolution 2 taps (2 Hk K + Hv V), the scan (`scan_required_flops` a
    token), W_o 2 Hv V D;
    attention: W_q with its gate 2 D 2 H hd, W_k and W_v 2 D 2 Hkv hd, causal
    scores and values at the half they require, 2 T H (hd + hd) / 2, W_o 2 H
    hd D;
    experts: the router 2 D (routed experts); the picks HELD HERE at the even
    share, picks a token x held / routed, each 3 * 2 D F_e; the shared
    expert 3 * 2 D F_s and its gate 2 D on every token;
    head: 2 D V over the vocabulary slice held."""
    c = config
    D, T = c["hidden_size"], seq
    Hk, Hv, K, V = _scan_sizes(c)
    taps = c["linear_conv_kernel_dim"]
    H, Hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    routed = c.get("num_routed_experts", c["num_experts"])
    held_picks = c["num_experts_per_tok"] * c["num_experts"] / routed
    conv_dim = 2 * Hk * K + Hv * V
    return {
        "gdn": (2 * D * (conv_dim + Hv * V) + 2 * D * 2 * Hv
                + 2 * taps * conv_dim
                + scan_required_flops(1, T, Hk, Hv, K, V, chunk) / 3.0 / T
                + 2 * Hv * V * D),
        "attention": (2 * D * 2 * H * hd + 2 * D * 2 * Hkv * hd
                      + T * H * 2 * hd + 2 * H * hd * D),
        "experts": (2 * D * routed
                    + held_picks * 6 * D * c["moe_intermediate_size"]
                    + 6 * D * c["shared_expert_intermediate_size"] + 2 * D),
        "head": 2 * D * c["vocab_size"]}


def flops_per_token(config, seq, chunk=64):
    """TRAINING FLOPs a token of the cut config.json describes, forward plus
    backward = 3 x forward; recomputation not counted."""
    by = forward_flops_by_part(config, seq, chunk)
    mixers = mixers_of(config)
    return 3.0 * (sum(by[m] for m in mixers) + len(mixers) * by["experts"]
                  + by["head"])


def scan_roofline_pct(scan_ms_per_step, config, traffic, device_kind,
                      chunk=64):
    """The share of its roofline the scan reaches: the least time the chip
    could take for every gdn layer's scan of a step (the larger of required
    operations over peak FLOP/s and required bytes over peak bytes/s), over
    `scan_ms_per_step`, the device time measured under `hetu_gdn_scan`
    (recomputation in the time, not in the requirement)."""
    B, T = traffic["sequences"], traffic["seq_len"]
    sizes = _scan_sizes(config)
    peak = peaks.peaks(device_kind)
    layers = mixers_of(config).count("gdn")
    least_s = layers * max(
        scan_required_flops(B, T, *sizes, chunk) / (peak["tflops"] * 1e12),
        scan_required_bytes(B, T, *sizes) / (peak["gbs"] * 1e9))
    return 100.0 * least_s / (scan_ms_per_step / 1e3)


# -- in a trace: `scopes.py`'s reader over SCOPES ---------------------------------

scope_of = functools.partial(scopes.scope_of, SCOPES)
reduce_scopes = functools.partial(scopes.reduce_scopes, SCOPES)
render = functools.partial(scopes.render, SCOPES)


def for_run(run):
    """The reduced table of a traced run's own trace, or None."""
    return scopes.for_run(SCOPES, run)


def scope_ms(run, *which):
    return scopes.scope_ms(for_run(run), *which)


def time_pct(run):
    return scopes.time_pct(for_run(run))


if __name__ == "__main__":
    sys.exit(scopes.main(SCOPES, "python -m benchmark.reduce.gdn",
                         sys.argv[1:]))
