"""Window and full attention in one stack, in a trace and on paper: device
self time under the program's three scopes (`hetu_swa_attn`, a window
layer's attention core; `hetu_attn_rope`, the rotation of q and k;
`hetu_attn_gate`, the per-head gate: written in `transformer._attention`,
`_split_heads` and `_window`), by step phase; the flash kernels' calls and
time, the window layers' (under `hetu_swa_attn`) apart from the full
layers' (under `hetu_blk_attn`); the program's own counter of the pairs its
kernels compute; and the operations and bytes each part REQUIRES from its
shapes alone.

An op's scope here is the INNERMOST segment of its `op_name` path that is one
of the three, as `reduce/mla.py` reads its own (the rotation's lies inside
`hetu_blk_qkv`). Reads `inside.read_inside`'s ops through
`inside._reduce_chip` (self times, phases) and edits nothing. A program that
lacks the scopes (any other model; the parent of the PR that added them)
reads as "nothing": every function returns None and does not raise.

Both rooflines count the pairs the MATHEMATICS keeps whatever implements
them: a window layer's sum over t of min(t + 1, W), a full layer's T (T + 1)
/ 2. A kernel that computes whole tiles, or every causal tile under a mask,
reads the lower for it, and neither share can read over 100 %.

`python -m benchmark.reduce.swa <trace dir>` prints the table.
"""
import functools
import os
import sys
import traceback

from . import block, inside
from .dsa import _least_s, attn_bwd_flops, attn_fwd_flops   # noqa: F401
from .mla import _flash_kernel, causal_pairs
from .trace import newest_xplane

# a copy of the program's vocabulary, as in inside.py
SWA, ROPE, GATE = SCOPES = ("hetu_swa_attn", "hetu_attn_rope",
                            "hetu_attn_gate")
BLK_ATTN = "hetu_blk_attn"
PHASES = ("fwd", "recompute", "bwd")
FLASH_FWD, FLASH_BWD = "flash_fwd", "flash_bwd"
WINDOW, FULL = "sliding_attention", "full_attention"


# -- on paper -------------------------------------------------------------------

def kept_pairs(seq, window=None):
    """(query, key) pairs a causal sequence KEEPS under a sliding window:
    query t keeps the min(t + 1, window) keys t - window < s <= t; every
    causal pair without one."""
    w = min(seq, window or seq)
    return w * (w + 1) / 2.0 + (seq - w) * float(w)


# a call's operations over `pairs` pairs, forward 4 and backward 10 a (pair,
# head, column), are `reduce/dsa.py`'s `attn_fwd_flops` / `attn_bwd_flops`
# (imported: learned sparse attention counts its kept pairs the same way)

def attn_fwd_bytes(batch, heads, kv_heads, seq, head_dim, itemsize=2):
    """HBM bytes one forward call requires: q read and o written at the
    query heads, k and v read once at the k/v heads (a grouped head's
    repeat is the program's choice), the row statistic written."""
    return batch * seq * (itemsize * head_dim * 2 * (heads + kv_heads)
                          + 4 * heads)


def attn_bwd_bytes(batch, heads, kv_heads, seq, head_dim, itemsize=2):
    """Its backward's: q, o, dO read and dq written at the query heads, k,
    v read and dk, dv written at the k/v heads, the statistic read."""
    return batch * seq * (itemsize * head_dim * 4 * (heads + kv_heads)
                          + 4 * heads)


def layers_of(config):
    """{layer type: (layers, query heads)} of a laguna config.json."""
    out = {}
    for t, h in zip(config["layer_types"],
                    config["num_attention_heads_per_layer"]):
        n, heads = out.get(t, (0, h))
        out[t] = (n + 1, heads)
    return out


def pairs_of(config, seq):
    """{layer type: the pairs a sequence keeps in one layer of it}."""
    return {WINDOW: kept_pairs(seq, config["sliding_window"]),
            FULL: causal_pairs(seq)}


def forward_flops(config, seq):
    """The forward pass's REQUIRED operations of one sequence of `seq`
    tokens of a laguna config.json CUT TO A SHARE, by part: the attention
    cores of each layer type over the pairs it keeps; attention's
    projections (q, k, v, o) and gates; the dense layers' MLP; the expert
    layers' shared expert, router and the picks HELD HERE at the even share
    (k * held / routed a token); the head over the vocabulary held."""
    c = config
    D, G, d = c["hidden_size"], c["num_key_value_heads"], c["head_dim"]
    kinds, pairs = layers_of(c), pairs_of(c, seq)
    sparse = c["mlp_layer_types"].count("sparse")
    routed = c.get("num_routed_experts", c["num_experts"])
    held_picks = c["num_experts_per_tok"] * c["num_experts"] / routed
    out = {t + "_core": n * attn_fwd_flops(1, h, pairs[t], d)
           for t, (n, h) in kinds.items()}
    out["attention_proj_and_gate"] = seq * sum(
        n * (2 * D * (h * d + 2 * G * d) + 2 * h * d * D
             + 2 * D * h * bool(c.get("gating")))
        for n, h in kinds.values())
    out["dense_mlp"] = seq * (len(c["mlp_layer_types"]) - sparse) * (
        6 * D * c["intermediate_size"])
    out["experts"] = seq * sparse * (
        2 * D * routed + 6 * D * c.get("shared_expert_intermediate_size", 0)
        + held_picks * 6 * D * c["moe_intermediate_size"])
    out["head"] = seq * 2 * D * c["vocab_size"]
    return out


def laguna_train_flops_per_token(config, seq):
    """Training FLOPs per token of a laguna config.json CUT TO A SHARE;
    recomputation not counted. The weights' parts (`forward_flops` but for
    the cores) forward plus backward = 3 x forward; the cores over the pairs
    each layer type KEEPS, forward 4 and backward 10 * d * H a pair. The
    gate and the held picks count once."""
    fwd = forward_flops(config, seq)
    cores = sum(v for k, v in fwd.items() if k.endswith("_core"))
    return (3.0 * (sum(fwd.values()) - cores) + 3.5 * cores) / seq


# -- in a trace -----------------------------------------------------------------

def scope_of(op_name):
    """The innermost segment of an `op_name` path that is one of SCOPES."""
    for segment in reversed(op_name.split("/")):
        m = block._WRAPPED.match(segment)
        if m and m.group(1) in SCOPES:
            return m.group(1)
    return None


def _flash_of(op_name):
    """Which layer type's flash call an op is: a window layer's runs under
    `hetu_swa_attn`, a full layer's under `hetu_blk_attn`."""
    if f"/{SWA}/" in op_name:
        return WINDOW
    if f"/{BLK_ATTN}/" in op_name:
        return FULL
    return None


def reduce_swa(raw, steps):
    """{"steps", "device_self_ms_per_step", "scope_ms_per_step": {scope:
    {phase: ms}} (the kernels under `hetu_swa_attn` among it), "time_pct"
    (`hetu_swa_attn`'s of device self time), "flash": {layer type:
    {"seconds", "fwd_calls", "bwd_calls", "kernels": {kernel: [calls a step,
    ms a step]}}}} from `inside.read_inside`'s form, summed over the traced
    steps, mean over chips; None where no op carries one of the three
    scopes. A backward is ONE required computation however many kernels
    share it: the kernel with the most calls counts them."""
    chips = raw["chips"]
    steps, n = max(int(steps), 1), max(len(chips), 1)
    scope_ns = {s: dict.fromkeys(PHASES, 0.0) for s in SCOPES}
    flash = {WINDOW: {}, FULL: {}}
    self_ns = found_ns = 0.0
    for chip in chips:
        insts, _busy = inside._reduce_chip(chip["ops"])
        for r in insts.values():
            self_ns += r["self_ns"]
            kernel = _flash_kernel(r)
            which = _flash_of(r["op_name"]) if kernel else None
            if which:
                row = flash[which].setdefault(kernel, [0, 0.0])
                row[0] += r["calls"]
                row[1] += r["self_ns"]
            scope = scope_of(r["op_name"])
            if scope is None or r["phase"] not in PHASES:
                continue
            found_ns += r["self_ns"]
            scope_ns[scope][r["phase"]] += r["self_ns"]
    if not found_ns:
        return None
    per_step = 1e6 * n * steps

    def table(kernels):
        bwd = [c for k, (c, _) in kernels.items() if k.startswith(FLASH_BWD)]
        return {"seconds": sum(ns for _, ns in kernels.values()) / 1e9 / n,
                "fwd_calls": kernels.get(FLASH_FWD, [0])[0] / n,
                "bwd_calls": max(bwd, default=0) / n,
                "kernels": {k: [c / n / steps, ns / per_step]
                            for k, (c, ns) in sorted(kernels.items())}}

    return {
        "steps": steps,
        "device_self_ms_per_step": self_ns / per_step,
        "scope_ms_per_step": {s: {p: ns / per_step for p, ns in by.items()}
                              for s, by in scope_ns.items()},
        "time_pct": 100.0 * sum(scope_ns[SWA].values()) / self_ns,
        "flash": {which: table(kernels) for which, kernels in flash.items()},
    }


@functools.lru_cache(maxsize=4)
def _reduced(path, steps):
    return reduce_swa(inside.read_inside(path), steps)


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
    """Device self time a step under `scopes`, all phases; None without."""
    r = for_run(run)
    if not r:
        return None
    return sum(sum(r["scope_ms_per_step"][s].values()) for s in scopes)


def attn_roofline_pct(flash, which, config, traffic, device_kind):
    """The flash calls of layer type `which` counted in the trace x the
    least time the pairs that type KEEPS require at the published widths (a
    forward run again under `remat` counted as run; compute-bound at these
    shapes: a window layer's forward 1.37 ms of operations against 0.74 ms
    of bytes at 16,384 tokens) over their device time, in percent."""
    from . import peaks
    if not flash["seconds"] or which not in layers_of(config):
        return None
    c, T, B = config, traffic["seq_len"], traffic["sequences"]
    heads, G, d = (layers_of(c)[which][1], c["num_key_value_heads"],
                   c["head_dim"])
    pairs, peak = pairs_of(c, T)[which], peaks.peaks(device_kind)
    least = (flash["fwd_calls"] * _least_s(
        attn_fwd_flops(B, heads, pairs, d),
        attn_fwd_bytes(B, heads, G, T, d), peak)
        + flash["bwd_calls"] * _least_s(
            attn_bwd_flops(B, heads, pairs, d),
            attn_bwd_bytes(B, heads, G, T, d), peak))
    return 100.0 * least / flash["seconds"]


def roofline_of(run, which):
    """`attn_roofline_pct` of a traced run's flash calls of layer type
    `which`; None without a table."""
    r = for_run(run)
    if not r:
        return None
    cell = run["cell"]
    return attn_roofline_pct(r["flash"][which], which, cell.config,
                             cell.traffic, run["device"]["kind"])


def computed_pair_pct(run):
    """The program's counter (the adapter's `counters()["attn_pairs"]`): the
    pairs the window layers' forward kernels COMPUTE (measured by the check
    through `transformer.attention_visits`; whole tiles visited x tile size)
    over the pairs they keep, in percent; None where the program counts
    none."""
    counted = (run["counters"].get("attn_pairs") or {}).get("window")
    if not counted or not counted.get("kept"):
        return None
    return 100.0 * counted["computed"] / counted["kept"]


def render(r):
    if not r:
        return "no hetu_swa_attn / hetu_attn_rope / hetu_attn_gate scope " \
               "in this trace"
    lines = [f"{r['steps']} traced step(s); window layers' attention core "
             f"{r['time_pct']:.1f} % of {r['device_self_ms_per_step']:.3f} "
             "ms device self time a step",
             "scope                     fwd  recompute       bwd     total"]
    for s in SCOPES:
        by = r["scope_ms_per_step"][s]
        lines.append(f"  {s:<21}" + "".join(f"{by[p]:>10.3f}" for p in PHASES)
                     + f"{sum(by.values()):>10.3f}")
    lines.append("kernel                              calls a step    "
                 "ms a step")
    for which, t in r["flash"].items():
        for k, (calls, ms) in t["kernels"].items():
            lines.append(f"  {which + ' ' + k:<34}{calls:>12.1f}{ms:>13.3f}")
    return "\n".join(lines)


def main(argv):
    import argparse
    p = argparse.ArgumentParser(prog="python -m benchmark.reduce.swa")
    p.add_argument("trace", help="a trace dir or one .xplane.pb")
    p.add_argument("--steps", type=int, default=None)
    a = p.parse_args(argv)
    path = a.trace if os.path.isfile(a.trace) else newest_xplane(a.trace)
    raw = inside.read_inside(path)
    steps = a.steps or inside.reduce_inside(raw)["steps"]
    print(render(reduce_swa(raw, steps)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
