"""Learned sparse attention (a lightning indexer picks `topk` keys a query)
in a trace and on paper: device self time under the program's four
`hetu_dsa_*` scopes (written in `transformer._dsa_parts` and
`kernels/dsa.py`), by step phase; the flash kernels' calls and time; the
program's own counter of kept pairs; and the operations each part REQUIRES
from its shapes alone.

An op's scope here is the INNERMOST segment of its `op_name` path that is one
of the four, as `reduce/mla.py` reads its own: the index scores run once for
the selection and once more inside the loss (`.../hetu_dsa_loss/
hetu_dsa_index_scores/...`), and both are the index scores'. Reads
`inside.read_inside`'s ops through `inside._reduce_chip` (self times, phases)
and edits nothing. A program that lacks the scopes (any other model; the
parent of the PR that added them) reads as "nothing": every function returns
None and does not raise.

The attention's roofline counts the KEPT pairs whatever implements them: a
dense kernel under a mask computes every causal pair and reads low (at most
the kept share, 23.4 % at 16,384 tokens and 2,048 keys, times its own
efficiency), and that is the headroom a kernel that skips the dropped pairs
starts from. It can never read over 100 %.

`python -m benchmark.reduce.dsa <trace dir>` prints the table.
"""
import functools
import os
import sys
import traceback

from . import block, inside
from .mla import _flash_kernel, causal_pairs
from .trace import newest_xplane

# a copy of the program's vocabulary, as in inside.py
PROJ, SCORES, SELECT, LOSS = SCOPES = (
    "hetu_dsa_index_proj", "hetu_dsa_index_scores", "hetu_dsa_select",
    "hetu_dsa_loss")
PHASES = ("fwd", "recompute", "bwd")
FLASH_FWD, FLASH_BWD = "flash_fwd", "flash_bwd"
# the three Mosaic kernels of `kernels/dsa.py` by the names a trace gives them
# (one traced under `jax.vjp` reads `jvp_<name>_`); the backward's comes
# first, its name holds the forward's. A call is one block of ROWS queries
# (`kernels/dsa.row_block`) against a sequence's keys.
INDEX_BWD, INDEX, PROBS = KERNELS = (
    "dsa_index_scores_bwd", "dsa_index_scores", "dsa_head_probs")
ROWS = 512


# -- on paper -------------------------------------------------------------------

def kept_pairs(seq, topk):
    """(query, key) pairs a causal sequence KEEPS: a query t keeps min(t + 1,
    topk) of the keys it sees."""
    full = min(seq, topk)
    return full * (full + 1) / 2.0 + max(seq - topk, 0) * float(topk)


def attn_fwd_flops(batch, heads, pairs, head_dim):
    """One forward call of attention over `pairs` (query, key) pairs a
    (batch row, head): the scores q . k^T and p . v, a multiply-add each a
    pair and column."""
    return 4.0 * batch * heads * pairs * head_dim


def attn_bwd_flops(batch, heads, pairs, head_dim):
    """The backward of one such call: five products (the scores again, dP,
    dV, dQ, dK)."""
    return 10.0 * batch * heads * pairs * head_dim


def index_scores_flops(batch, index_heads, pairs, index_dim):
    """The index scores of `pairs` pairs a batch row: qI_j . kI a head."""
    return 2.0 * batch * index_heads * pairs * index_dim


def loss_target_flops(batch, heads, pairs, head_dim):
    """The loss's target over `pairs` pairs: q . k^T again, a head (the
    probabilities are exp(score - lse), summed over heads)."""
    return 2.0 * batch * heads * pairs * head_dim


def row_blocks(seq):
    """Calls of one of the three kernels a pass over a sequence of `seq`:
    blocks of the most rows up to ROWS, by halving, that divide it."""
    rows = ROWS
    while seq % rows:
        rows //= 2
    return seq // rows


def index_scores_bytes(index_heads, index_dim, seq, pairs, itemsize=2):
    """One pass of the index scores over a sequence: every query's heads
    (`itemsize` a column) and float32 weights read once, the one index key of
    a pair once a BLOCK of query rows, the score of every pair written once
    in float32."""
    rows = seq // row_blocks(seq)
    return (seq * index_heads * (index_dim * itemsize + 4)
            + pairs / rows * index_dim * itemsize + 4.0 * pairs)


def index_scores_bwd_flops(index_heads, pairs, index_dim):
    """The index scores' cotangents over `pairs` pairs a sequence, where the
    loss's gradient is not zero: dqI and dkI, a product each a head."""
    return 4.0 * index_heads * pairs * index_dim


def index_scores_bwd_bytes(index_heads, index_dim, seq, pairs, itemsize=2):
    """What the forward pass reads, the gradient on the score of every pair
    read once in float32, and dqI, dw (a query's) and dkI (a pair's key, once
    a block of rows) written in float32."""
    rows = seq // row_blocks(seq)
    return (seq * index_heads * (index_dim * itemsize + 4)
            + pairs / rows * index_dim * itemsize + 4.0 * pairs
            + 4.0 * seq * index_heads * (index_dim + 1)
            + 4.0 * pairs / rows * index_dim)


def loss_target_bytes(heads, kv_heads, head_dim, seq, pairs, itemsize=2):
    """One pass of the loss's target over a sequence: every query's heads
    and their float32 row statistic read once, a pair's key (at the k/v
    heads) once a block of query rows, the head-summed probability of every
    pair written once in float32."""
    rows = seq // row_blocks(seq)
    return (seq * heads * (head_dim * itemsize + 4)
            + pairs / rows * kv_heads * head_dim * itemsize + 4.0 * pairs)


def keye_forward_shares(config, seq):
    """Required forward work of ONE sequence in ONE layer, in FLOP, by part:
    `attention` over the kept pairs, `index_scores` over every causal pair,
    `loss_target` over the kept pairs, `rest` the projections (attention's,
    the indexer's), the router and the held experts at the even share."""
    c, sa = config, config["sa_config"]
    D, H, G, d = (c["hidden_size"], c["num_attention_heads"],
                  c["num_key_value_heads"], c["head_dim"])
    J, ci = sa["indexer_num_heads"], sa["indexer_head_dim"]
    kept = kept_pairs(seq, sa["topk"])
    return {"attention": attn_fwd_flops(1, H, kept, d),
            "index_scores": index_scores_flops(1, J, causal_pairs(seq), ci),
            "loss_target": loss_target_flops(1, H, kept, d),
            "rest": seq * _token_flops(c, D, H, G, d, J, ci)}


def _token_flops(c, D, H, G, d, J, ci):
    """A token's forward FLOPs in a layer outside the (query, key) pairs:
    Wq, Wk, Wv, Wo; the indexer's three projections; the router over all
    routed experts; the picks HELD HERE at the even share, k * held / routed
    a token (8 * 16 / 128 = 1), each 3 * 2*D*F_e."""
    routed = c.get("num_routed_experts", c["num_experts"])
    held_picks = c["num_experts_per_tok"] * c["num_experts"] / routed
    return (2 * D * (H * d + 2 * G * d) + 2 * H * d * D
            + 2 * D * (J * ci + ci + J) + 2 * D * routed
            + held_picks * 6 * D * c["moe_intermediate_size"])


def keye_train_flops_per_token(config, seq):
    """Training FLOPs per token of Keye-VL-2.0's language model CUT TO A
    SHARE, from its config.json; recomputation not counted. The weights'
    parts (`_token_flops`, the untied head over the vocabulary held) forward
    plus backward = 3 x forward. The pairs' parts a sequence, over `seq`:
    attention over the KEPT pairs, forward 4 and backward 10 * d * H a pair;
    the index scores over every causal pair once (2 * c * J) and their
    backward over the kept pairs, where alone the loss's gradient is not
    zero (4 * c * J); the loss's target once (2 * d * H, no gradient goes
    through it)."""
    c, sa = config, config["sa_config"]
    D, H, G, d = (c["hidden_size"], c["num_attention_heads"],
                  c["num_key_value_heads"], c["head_dim"])
    J, ci = sa["indexer_num_heads"], sa["indexer_head_dim"]
    kept, causal = kept_pairs(seq, sa["topk"]), causal_pairs(seq)
    pairs = (attn_fwd_flops(1, H, kept, d) + attn_bwd_flops(1, H, kept, d)
             + index_scores_flops(1, J, causal, ci)
             + 2 * index_scores_flops(1, J, kept, ci)
             + loss_target_flops(1, H, kept, d)) / seq
    return c["num_hidden_layers"] * (
        3.0 * _token_flops(c, D, H, G, d, J, ci) + pairs
    ) + 3.0 * 2 * D * c["vocab_size"]


# -- in a trace -----------------------------------------------------------------

def scope_of(op_name):
    """The innermost segment of an `op_name` path that is one of SCOPES."""
    for segment in reversed(op_name.split("/")):
        m = block._WRAPPED.match(segment)
        if m and m.group(1) in SCOPES:
            return m.group(1)
    return None


def reduce_dsa(raw, steps):
    """{"steps", "device_self_ms_per_step", "scope_ms_per_step": {scope:
    {phase: ms}}, "dsa_ms_per_step" (the four scopes and the flash kernels),
    "time_pct" (theirs of device self time), "flash": {"seconds",
    "fwd_calls", "bwd_calls", "kernels": {kernel: [calls, ms a step]}},
    "kernels": {one of KERNELS: {"calls", "seconds"}}} from
    `inside.read_inside`'s form, summed over the traced steps, mean over
    chips; None where no op carries one of the `hetu_dsa_*` scopes. A
    backward is ONE required computation however many kernels share it: the
    kernel with the most calls counts them."""
    chips = raw["chips"]
    steps, n = max(int(steps), 1), max(len(chips), 1)
    scope_ns = {s: dict.fromkeys(PHASES, 0.0) for s in SCOPES}
    kernels, own_kernels = {}, {}
    self_ns = dsa_ns = flash_ns = 0.0
    for chip in chips:
        insts, _busy = inside._reduce_chip(chip["ops"])
        for r in insts.values():
            self_ns += r["self_ns"]
            kernel = _flash_kernel(r)
            if kernel:
                flash_ns += r["self_ns"]
                row = kernels.setdefault(kernel, [0, 0.0])
                row[0] += r["calls"]
                row[1] += r["self_ns"]
                continue
            scope = scope_of(r["op_name"])
            if scope is None or r["phase"] not in PHASES:
                continue
            own = next((k for k in KERNELS if k in r["name"]), None)
            if own:
                row = own_kernels.setdefault(own, [0, 0.0])
                row[0] += r["calls"]
                row[1] += r["self_ns"]
            dsa_ns += r["self_ns"]
            scope_ns[scope][r["phase"]] += r["self_ns"]
    if not dsa_ns:
        return None
    per_step = 1e6 * n * steps
    bwd = [c for k, (c, _) in kernels.items() if k.startswith(FLASH_BWD)]
    return {
        "steps": steps,
        "device_self_ms_per_step": self_ns / per_step,
        "scope_ms_per_step": {s: {p: ns / per_step for p, ns in by.items()}
                              for s, by in scope_ns.items()},
        "dsa_ms_per_step": (dsa_ns + flash_ns) / per_step,
        "time_pct": 100.0 * (dsa_ns + flash_ns) / self_ns,
        "flash": {
            "seconds": flash_ns / 1e9 / n,
            "fwd_calls": kernels.get(FLASH_FWD, [0])[0] / n,
            "bwd_calls": max(bwd, default=0) / n,
            "kernels": {k: [c / n / steps, ns / per_step]
                        for k, (c, ns) in sorted(kernels.items())}},
        "kernels": {k: {"calls": c / n, "seconds": ns / 1e9 / n}
                    for k, (c, ns) in sorted(own_kernels.items())},
    }


@functools.lru_cache(maxsize=4)
def _reduced(path, steps):
    return reduce_dsa(inside.read_inside(path), steps)


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


def share_of(run, share, part):
    """A roofline share of a traced run: `share` (one of the three
    `*_roofline_pct` below) of the reduced table's `part` ("flash" or
    "kernels"); None without a table."""
    r = for_run(run)
    if not r:
        return None
    cell = run["cell"]
    return share(r[part], cell.config, cell.traffic, run["device"]["kind"])


def scope_ms(run, *scopes):
    """Device self time a step under `scopes`, all phases; None without."""
    r = for_run(run)
    if not r:
        return None
    return sum(sum(r["scope_ms_per_step"][s].values()) for s in scopes)


def kept_pair_pct(run):
    """The program's counter (`transformer.dsa_stats` on the last step's
    batch, the adapter's `counters()["dsa"]`): kept over causal pairs, all
    layers, in percent; None where the program counts none."""
    counted = run["counters"].get("dsa")
    if not counted:
        return None
    return 100.0 * sum(counted["kept_pairs"]) / sum(counted["causal_pairs"])


def attn_roofline_pct(flash, config, traffic, device_kind):
    """The flash calls counted in the trace x the operations the KEPT pairs
    require at the published widths (a forward run again under `remat`
    counted as run) over their device time x the published peak of this
    `device_kind`, in percent. Compute-bound."""
    from . import peaks
    if not flash["seconds"]:
        return None
    kept = kept_pairs(traffic["seq_len"], config["sa_config"]["topk"])
    shape = (traffic["sequences"], config["num_attention_heads"], kept,
             config["head_dim"])
    flops = (flash["fwd_calls"] * attn_fwd_flops(*shape)
             + flash["bwd_calls"] * attn_bwd_flops(*shape))
    peak = peaks.peaks(device_kind)["tflops"] * 1e12
    return 100.0 * flops / flash["seconds"] / peak


def _least_s(flops, nbytes, peak):
    """The least time the chip could take: the larger of the operations over
    its peak FLOP/s and the bytes over its peak bytes/s."""
    return max(flops / (peak["tflops"] * 1e12), nbytes / (peak["gbs"] * 1e9))


def index_roofline_pct(kernels, config, traffic, device_kind):
    """The index scores' two kernels' share of their roofline: the passes
    counted in the trace (a pass is `row_blocks` calls, one sequence of one
    layer) x the least time a pass could take (the forward over every causal
    pair, the backward over the kept pairs, where alone its cotangent is not
    zero) over their device time, in percent; a pass run again under `remat`
    counted as run. None where the trace has neither kernel."""
    from . import peaks
    sa, T = config["sa_config"], traffic["seq_len"]
    J, c = sa["indexer_num_heads"], sa["indexer_head_dim"]
    causal, kept = causal_pairs(T), kept_pairs(T, sa["topk"])
    peak = peaks.peaks(device_kind)
    least = {INDEX: _least_s(index_scores_flops(1, J, causal, c),
                             index_scores_bytes(J, c, T, causal), peak),
             INDEX_BWD: _least_s(index_scores_bwd_flops(J, kept, c),
                                 index_scores_bwd_bytes(J, c, T, kept), peak)}
    return _passes_roofline_pct(kernels, least, T)


def loss_roofline_pct(kernels, config, traffic, device_kind):
    """The loss's target kernel's share of its roofline, as
    `index_roofline_pct`: a pass rebuilds q . k of the kept pairs, a head."""
    from . import peaks
    c, T = config, traffic["seq_len"]
    H, G, d = (c["num_attention_heads"], c["num_key_value_heads"],
               c["head_dim"])
    kept = kept_pairs(T, c["sa_config"]["topk"])
    least = {PROBS: _least_s(loss_target_flops(1, H, kept, d),
                             loss_target_bytes(H, G, d, T, kept),
                             peaks.peaks(device_kind))}
    return _passes_roofline_pct(kernels, least, T)


def _passes_roofline_pct(kernels, least, seq):
    found = [k for k in least if kernels.get(k, {}).get("seconds")]
    if not found:
        return None
    return 100.0 * sum(kernels[k]["calls"] / row_blocks(seq) * least[k]
                       for k in found) / sum(kernels[k]["seconds"]
                                             for k in found)


def render(r):
    if not r:
        return "no hetu_dsa_* scope in this trace"
    lines = [f"{r['steps']} traced step(s); learned sparse attention (the "
             f"indexer's four parts + kernels) {r['dsa_ms_per_step']:.3f} ms "
             f"of {r['device_self_ms_per_step']:.3f} ms device self time a "
             f"step = {r['time_pct']:.1f} %",
             "scope                     fwd  recompute       bwd     total"]
    for s in SCOPES:
        by = r["scope_ms_per_step"][s]
        lines.append(f"  {s:<21}" + "".join(f"{by[p]:>10.3f}" for p in PHASES)
                     + f"{sum(by.values()):>10.3f}")
    lines.append("kernel              calls a step    ms a step")
    for k, (calls, ms) in r["flash"]["kernels"].items():
        lines.append(f"  {k:<18}{calls:>12.1f}{ms:>13.3f}")
    for k, row in r["kernels"].items():
        lines.append(f"  {k:<22}{row['calls'] / r['steps']:>8.1f}"
                     f"{1e3 * row['seconds'] / r['steps']:>13.3f}")
    return "\n".join(lines)


def main(argv):
    import argparse
    p = argparse.ArgumentParser(prog="python -m benchmark.reduce.dsa")
    p.add_argument("trace", help="a trace dir or one .xplane.pb")
    p.add_argument("--steps", type=int, default=None)
    a = p.parse_args(argv)
    path = a.trace if os.path.isfile(a.trace) else newest_xplane(a.trace)
    raw = inside.read_inside(path)
    steps = a.steps or inside.reduce_inside(raw)["steps"]
    print(render(reduce_dsa(raw, steps)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
