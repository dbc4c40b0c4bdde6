"""Kimi Delta Attention in a trace and on paper: device self time under the
five scopes the kind adds to the program's vocabulary (`hetu_kda_proj`, the
four large and five small projections; `hetu_kda_conv`, the three causal
convolutions and SiLU; `hetu_kda_gate`, the log-decay, beta, the L2 norms and
the output's gated head norm; `hetu_kda_scan`, the chunked gated delta rule,
with `hetu_kda_solve`, the triangular system, INSIDE it: written in
`transformer._kda` and `models/kda.py`), by step phase; the scan's required
operations and bytes FROM ITS SHAPES, whatever implements it; and the
operations a token of Kimi Linear requires, by layer.

An op's scope here is the INNERMOST segment of its `op_name` path that is one
of the five, as `reduce/nemotron_h.py` reads its own: the solve's time is
its own row, and the scan's total is the two rows' sum. Reads
`inside.read_inside`'s ops through `inside._reduce_chip` (self times, phases)
and edits nothing. A program that lacks the scopes (any other model; the
parent of the PR that added them) reads as "nothing": every function returns
None and does not raise.

`python -m benchmark.reduce.kda <trace dir>` prints the table.
"""
import functools
import os
import sys
import traceback

from . import block, inside, peaks
from .trace import newest_xplane

# a copy of the program's vocabulary, as in inside.py
PROJ, CONV, GATE, SCAN, SOLVE = SCOPES = (
    "hetu_kda_proj", "hetu_kda_conv", "hetu_kda_gate", "hetu_kda_scan",
    "hetu_kda_solve")
PHASES = ("fwd", "recompute", "bwd")


# -- on paper -------------------------------------------------------------------

def scan_required_flops(batch, seq, heads, key_dim, value_dim, chunk):
    """Training FLOPs of one gated-delta-rule scan, forward plus backward = 3
    x forward; recomputation not counted. Forward, a head, with C = chunk and
    seq / C chunks, 2 a multiply-add:

    the two pairwise products (k.k for the system, q.k for the output) at
    the causal half they require, C (C + 1) / 2 pairs of positions, 2 *
    key_dim each;
    the triangular system by forward substitution on its C (C - 1) / 2
    entries, against value_dim + key_dim right-hand columns ([V | K]);
    the state's three products a position: W S (key_dim x value_dim), q S and
    the update k u^T (the same each);
    the output's in-chunk product P U at its causal half, 2 * value_dim a
    pair.
    The elementwise work (exp of the decays' differences, the norms, the
    cumulated sum) is not counted: it runs on the VPU, not the MXU."""
    pairs, below = chunk * (chunk + 1) // 2, chunk * (chunk - 1) // 2
    a_chunk = (2 * pairs * 2.0 * key_dim
               + below * 2.0 * (value_dim + key_dim)
               + pairs * 2.0 * value_dim)
    a_position = 3 * 2.0 * key_dim * value_dim
    return 3.0 * batch * heads * ((seq // chunk) * a_chunk
                                  + seq * a_position)


def scan_required_bytes(batch, seq, heads, key_dim, value_dim, itemsize=2):
    """Bytes one scan must move, forward plus backward = 3 x forward (the
    backward pass reads the forward's operands and the output's cotangent and
    writes every operand's): forward reads q and k (key_dim a head and
    position) and v (value_dim) at the compute dtype, the log-decay (float32,
    key_dim: a CHANNEL) and beta (float32 a head), and writes o like v. The
    chunk moves nothing: the pairwise decays, the system, its solution and
    the carried state are made and used on the chip."""
    a_position = heads * (itemsize * (2 * key_dim + 2 * value_dim)
                          + 4 * key_dim + 4)
    return 3.0 * batch * seq * a_position


def mixers_of(config):
    """"kda" or "mla" a layer: layers 1 .. `num_hidden_layers`, from one."""
    la = config["linear_attn_config"]
    return ["kda" if i in la["kda_layers"] else "mla"
            for i in range(1, config["num_hidden_layers"] + 1)]


def forward_flops_by_part(config, seq, chunk=64):
    """Forward FLOPs a TOKEN of ONE layer's part, and of the head, from
    config.json and the sequence length; the matmuls' 2 a multiply-add,
    elementwise work not counted:

    kda: q, k, v 2 D (3 H K), the two low-rank gates 2 * (2 D R + 2 R H K), R
    = K, beta 2 D H, the three `taps`-tap convolutions 2 taps (3 H K), the
    scan (`scan_required_flops` a token), W_o 2 H K D;
    mla: W_q 2 D heads (nope + rope), W_kv_a 2 D (rank + rope), W_kv_b 2 rank
    heads (nope + v), causal scores and values at the half they require, 2 T
    heads (nope + rope + v) / 2, W_o 2 heads v D;
    dense: the SwiGLU MLP 3 * 2 D F;
    experts: the router 2 D (routed experts); the picks HELD HERE at the even
    share, picks a token x held / routed, each 3 * 2 D F_e; the shared
    expert 3 * 2 D F_s on every token;
    head: 2 D V over the vocabulary slice held."""
    c = config
    la = c["linear_attn_config"]
    D, T = c["hidden_size"], seq
    H, K, taps = la["num_heads"], la["head_dim"], la["short_conv_kernel_size"]
    heads, rank = c["num_attention_heads"], c["kv_lora_rank"]
    nope, rope, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    routed = c.get("num_routed_experts", c["num_experts"])
    held_picks = c["num_experts_per_token"] * c["num_experts"] / routed
    Fe = c["moe_intermediate_size"]
    return {
        "kda": (2 * D * 3 * H * K + 2 * (2 * D * K + 2 * K * H * K)
                + 2 * D * H + 2 * taps * 3 * H * K
                + scan_required_flops(1, T, H, K, K, chunk) / 3.0 / T
                + 2 * H * K * D),
        "mla": (2 * D * heads * (nope + rope) + 2 * D * (rank + rope)
                + 2 * rank * heads * (nope + vd)
                + T * heads * (nope + rope + vd) + 2 * heads * vd * D),
        "dense": 6 * D * c["intermediate_size"],
        "experts": (2 * D * routed + held_picks * 6 * D * Fe
                    + c.get("num_shared_experts", 0) * 6 * D * Fe),
        "head": 2 * D * c["vocab_size"]}


def flops_per_token(config, seq, chunk=64):
    """TRAINING FLOPs a token of the cut config.json describes, forward plus
    backward = 3 x forward; recomputation not counted."""
    by = forward_flops_by_part(config, seq, chunk)
    dense = min(config.get("first_k_dense_replace", 0),
                config["num_hidden_layers"])
    mixers = mixers_of(config)
    return 3.0 * (sum(by[m] for m in mixers) + dense * by["dense"]
                  + (len(mixers) - dense) * by["experts"] + by["head"])


def scan_roofline_pct(scan_ms_per_step, config, traffic, device_kind,
                      chunk=64):
    """The share of its roofline the scan reaches: the least time the chip
    could take for every kda layer's scan of a step (the larger of required
    operations over peak FLOP/s and required bytes over peak bytes/s), over
    `scan_ms_per_step`, the device time measured under `hetu_kda_scan` (the
    solve's included; recomputation in the time, not in the requirement)."""
    la = config["linear_attn_config"]
    B, T = traffic["sequences"], traffic["seq_len"]
    H, K = la["num_heads"], la["head_dim"]
    peak = peaks.peaks(device_kind)
    layers = mixers_of(config).count("kda")
    least_s = layers * max(
        scan_required_flops(B, T, H, K, K, chunk) / (peak["tflops"] * 1e12),
        scan_required_bytes(B, T, H, K, K) / (peak["gbs"] * 1e9))
    return 100.0 * least_s / (scan_ms_per_step / 1e3)


# -- in a trace -----------------------------------------------------------------

def scope_of(op_name):
    """The innermost segment of an `op_name` path that is one of SCOPES."""
    for segment in reversed(op_name.split("/")):
        m = block._WRAPPED.match(segment)
        if m and m.group(1) in SCOPES:
            return m.group(1)
    return None


def reduce_scopes(raw, steps):
    """{"steps", "device_self_ms_per_step", "scope_ms_per_step": {scope:
    {phase: ms}}} from `inside.read_inside`'s form, mean over chips; None
    where no op carries one of the five scopes."""
    chips = raw["chips"]
    steps, n = max(int(steps), 1), max(len(chips), 1)
    scope_ns = {s: dict.fromkeys(PHASES, 0.0) for s in SCOPES}
    self_ns = found_ns = 0.0
    for chip in chips:
        insts, _busy = inside._reduce_chip(chip["ops"])
        for r in insts.values():
            self_ns += r["self_ns"]
            scope = scope_of(r["op_name"])
            if scope is None or r["phase"] not in PHASES:
                continue
            found_ns += r["self_ns"]
            scope_ns[scope][r["phase"]] += r["self_ns"]
    if not found_ns:
        return None
    per_step = 1e6 * n * steps
    return {"steps": steps, "device_self_ms_per_step": self_ns / per_step,
            "scope_ms_per_step": {
                s: {p: ns / per_step for p, ns in by.items()}
                for s, by in scope_ns.items()}}


@functools.lru_cache(maxsize=4)
def _reduced(path, steps):
    return reduce_scopes(inside.read_inside(path), steps)


def for_run(run):
    """The reduced table of a traced run's own trace, or None (an
    end-to-end run, no trace, no scope, a trace this file cannot read: the
    reason goes to stderr and the reader leaves its metric out)."""
    t = run.get("trace")
    if not t:
        return None
    cell = run["cell"]
    try:
        path = newest_xplane(os.path.join(cell.bench_dir, ".cache", "trace",
                                          cell.name))
        return _reduced(path, t.get("steps") or 1)
    except Exception:       # noqa: BLE001 - a reader returns nothing instead
        traceback.print_exc()
        return None


def scope_ms(run, *scopes):
    """Device self time a step under `scopes` (each op counted under its
    innermost one), all phases; None without."""
    r = for_run(run)
    if not r:
        return None
    return sum(sum(r["scope_ms_per_step"][s].values()) for s in scopes) or None


def time_pct(run):
    """The five scopes' share of the device self time a step, in %."""
    r = for_run(run)
    if not r or not r["device_self_ms_per_step"]:
        return None
    return 100.0 * sum(sum(by.values()) for by in r[
        "scope_ms_per_step"].values()) / r["device_self_ms_per_step"]


def render(r):
    if not r:
        return "no hetu_kda_* scope in this trace"
    lines = [f"{r['steps']} traced step(s); "
             f"{r['device_self_ms_per_step']:.3f} ms device self time a step",
             "scope                      fwd  recompute       bwd     total"]
    for s in SCOPES:
        by = r["scope_ms_per_step"][s]
        lines.append(f"  {s:<22}" + "".join(f"{by[p]:>10.3f}" for p in PHASES)
                     + f"{sum(by.values()):>10.3f}")
    return "\n".join(lines)


def main(argv):
    import argparse
    p = argparse.ArgumentParser(prog="python -m benchmark.reduce.kda")
    p.add_argument("trace", help="a trace dir or one .xplane.pb")
    p.add_argument("--steps", type=int, default=None)
    a = p.parse_args(argv)
    path = a.trace if os.path.isfile(a.trace) else newest_xplane(a.trace)
    raw = inside.read_inside(path)
    steps = a.steps or inside.reduce_inside(raw)["steps"]
    print(render(reduce_scopes(raw, steps)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
