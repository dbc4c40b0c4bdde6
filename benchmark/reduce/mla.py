"""Latent attention (MLA) and the shared expert in a trace and on paper:
device self time under the program's three `hetu_mla_*` scopes (written
inside `transformer._mla_qkv`, each nested in `hetu_blk_qkv`) and under
`hetu_moe_shared` (the always-on branch of an expert layer, `transformer.
_moe_mlp`), by step phase; the flash kernels' calls and time at TWO head
widths (q . k at `qk_head_dim`, p . v at `v_head_dim`); and the operations
each requires from its shapes alone.

An op's scope here is the INNERMOST segment of its `op_name` path that is
one of the four, as `reduce/block.py` reads its own (`.../hetu_moe_shared/
hetu_blk_mlp_up/...` is the shared expert's). Reads `inside.read_inside`'s
ops through `inside._reduce_chip` (self times, phases) and edits nothing. A
program that lacks the scopes (any other model; the parent of the PR that
added them) reads as "nothing": every function returns None and does not
raise.

`reduce/kernel_flops.py` counts every key of a flash call, because its
cells are bidirectional; a causal kernel skips the key blocks above the
diagonal, so read against that count it would pass 100 %. The counts here
are the CAUSAL ones, at the two widths the model states.

`python -m benchmark.reduce.mla <trace dir>` prints the table.
"""
import functools
import os
import sys
import traceback

from . import block, inside
from .trace import MOSAIC, family, newest_xplane

# a copy of the program's vocabulary, as in inside.py
Q, KV_DOWN, KV_UP = MLA = ("hetu_mla_q", "hetu_mla_kv_down", "hetu_mla_kv_up")
SHARED = "hetu_moe_shared"
SCOPES = MLA + (SHARED,)
PHASES = ("fwd", "recompute", "bwd")
FLASH_FWD, FLASH_BWD = "flash_fwd", "flash_bwd"


# -- on paper -------------------------------------------------------------------

def causal_pairs(seq):
    """(query, key) pairs a causal sequence has: a query sees itself and
    what came before."""
    return seq * (seq + 1) / 2.0


def mla_attn_fwd_flops(batch, heads, seq, qk_dim, v_dim):
    """One forward call of causal attention at two head widths: the scores
    q . k^T at `qk_dim` columns and p . v at `v_dim`, a multiply-add each a
    pair and column."""
    return 2.0 * batch * heads * causal_pairs(seq) * (qk_dim + v_dim)


def mla_attn_bwd_flops(batch, heads, seq, qk_dim, v_dim):
    """The backward of one such call: five products at their own widths, the
    scores again, dQ and dK at `qk_dim`, dP and dV at `v_dim`. The program's
    two kernels (dq; dk+dv) each rebuild the scores and dP, seven products in
    all: the two extra are not required."""
    return 2.0 * batch * heads * causal_pairs(seq) * (3 * qk_dim + 2 * v_dim)


def kanana_train_flops_per_token(config, seq):
    """Training FLOPs per token of a DeepSeek-V3 style decoder CUT TO A
    SHARE, from its config.json, forward plus backward = 3 x forward;
    recomputation not counted. Per token, forward:

    latent attention, every layer: Wq 2*D*H*qk, Wkv_a 2*D*(rank + rope),
    Wkv_b 2*rank*H*(nope + v), Wo 2*H*v*D; causal scores and values at the
    half they require, T*H*(qk + v);
    a dense layer's MLP 3 * 2*D*F;
    an expert layer: the router 2*D*routed, the shared expert 3 * 2*D*
    (n_shared * F_e), and the picks HELD HERE at the even share, k * held /
    routed a token (kanana-2-30b-a3b: 6 * 16 / 128 = 0.75), each 3 * 2*D*F_e:
    what an even load requires of this chip, the same on every seed (the
    counted picks are `moe_held_pick_pct`'s);
    the untied head over the vocabulary held, 2*D*V once."""
    c = config
    D, H, T = c["hidden_size"], c["num_attention_heads"], seq
    rank, nope, rope, v = (c["kv_lora_rank"], c["qk_nope_head_dim"],
                           c["qk_rope_head_dim"], c["v_head_dim"])
    layers = c["num_hidden_layers"]
    dense = min(c.get("first_k_dense_replace", 0), layers)
    routed = c.get("num_routed_experts", c["n_routed_experts"])
    held_picks = c["num_experts_per_tok"] * c["n_routed_experts"] / routed
    Fe = c["moe_intermediate_size"]
    attention = (2 * D * H * (nope + rope) + 2 * D * (rank + rope)
                 + 2 * rank * H * (nope + v) + 2 * H * v * D
                 + T * H * (nope + rope + v))
    experts = (2 * D * routed + 6 * D * c.get("n_shared_experts", 0) * Fe
               + held_picks * 6 * D * Fe)
    return 3.0 * (layers * attention + dense * 6 * D * c["intermediate_size"]
                  + (layers - dense) * experts + 2 * D * c["vocab_size"])


# -- in a trace -----------------------------------------------------------------

def scope_of(op_name):
    """The innermost segment of an `op_name` path that is one of SCOPES."""
    for segment in reversed(op_name.split("/")):
        m = block._WRAPPED.match(segment)
        if m and m.group(1) in SCOPES:
            return m.group(1)
    return None


def _flash_kernel(record):
    """"flash_fwd", "flash_bwd_dq", ... of a Mosaic record; None otherwise."""
    if record["kind"] != MOSAIC:
        return None
    kernel = family(record["name"])
    at = kernel.find("flash_")
    return kernel[at:] if at >= 0 else None


def reduce_mla(raw, steps):
    """{"steps", "device_self_ms_per_step", "scope_ms_per_step": {scope:
    {phase: ms}}, "mla_ms_per_step" (the three projections and the flash
    kernels), "time_pct" (theirs of device self time), "flash": {"seconds",
    "fwd_calls", "bwd_calls", "kernels": {kernel: [calls, ms a step]}}} from
    `inside.read_inside`'s form, summed over the traced steps, mean over
    chips; None where no op carries one of the `hetu_mla_*` scopes. A
    backward is ONE required computation however many kernels share it (dq;
    dk+dv): the kernel with the most calls counts them."""
    chips = raw["chips"]
    steps, n = max(int(steps), 1), max(len(chips), 1)
    scope_ns = {s: dict.fromkeys(PHASES, 0.0) for s in SCOPES}
    kernels = {}
    self_ns = mla_ns = flash_ns = 0.0
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
            if scope in MLA:
                mla_ns += r["self_ns"]
            scope_ns[scope][r["phase"]] += r["self_ns"]
    if not mla_ns:
        return None
    per_step = 1e6 * n * steps
    bwd = [c for k, (c, _) in kernels.items() if k.startswith(FLASH_BWD)]
    return {
        "steps": steps,
        "device_self_ms_per_step": self_ns / per_step,
        "scope_ms_per_step": {s: {p: ns / per_step for p, ns in by.items()}
                              for s, by in scope_ns.items()},
        "mla_ms_per_step": (mla_ns + flash_ns) / per_step,
        "time_pct": 100.0 * (mla_ns + flash_ns) / self_ns,
        "flash": {
            "seconds": flash_ns / 1e9 / n,
            "fwd_calls": kernels.get(FLASH_FWD, [0])[0] / n,
            "bwd_calls": max(bwd, default=0) / n,
            "kernels": {k: [c / n / steps, ns / per_step]
                        for k, (c, ns) in sorted(kernels.items())}},
    }


@functools.lru_cache(maxsize=4)
def _reduced(path, steps):
    return reduce_mla(inside.read_inside(path), steps)


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


def attn_roofline_pct(flash, config, traffic, device_kind):
    """The flash calls counted in the trace x the operations each REQUIRES
    at the published widths (a forward run again under `remat` counted as
    run) over their device time x the published peak of this `device_kind`,
    in percent. Compute-bound: 192 + 128 columns a pair against 2 bytes a
    column read once a block."""
    from . import peaks
    if not flash["seconds"]:
        return None
    shape = (traffic["sequences"], config["num_attention_heads"],
             traffic["seq_len"],
             config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
             config["v_head_dim"])
    flops = (flash["fwd_calls"] * mla_attn_fwd_flops(*shape)
             + flash["bwd_calls"] * mla_attn_bwd_flops(*shape))
    peak = peaks.peaks(device_kind)["tflops"] * 1e12
    return 100.0 * flops / flash["seconds"] / peak


def render(r):
    if not r:
        return "no hetu_mla_* scope in this trace"
    lines = [f"{r['steps']} traced step(s); latent attention (projections + "
             f"kernels) {r['mla_ms_per_step']:.3f} ms of "
             f"{r['device_self_ms_per_step']:.3f} ms device self time a "
             f"step = {r['time_pct']:.1f} %",
             "scope                  fwd  recompute       bwd     total"]
    for s in SCOPES:
        by = r["scope_ms_per_step"][s]
        lines.append(f"  {s:<18}" + "".join(f"{by[p]:>10.3f}" for p in PHASES)
                     + f"{sum(by.values()):>10.3f}")
    lines.append("kernel              calls a step    ms a step")
    for k, (calls, ms) in r["flash"]["kernels"].items():
        lines.append(f"  {k:<18}{calls:>12.1f}{ms:>13.3f}")
    return "\n".join(lines)


def main(argv):
    import argparse
    p = argparse.ArgumentParser(prog="python -m benchmark.reduce.mla")
    p.add_argument("trace", help="a trace dir or one .xplane.pb")
    p.add_argument("--steps", type=int, default=None)
    a = p.parse_args(argv)
    path = a.trace if os.path.isfile(a.trace) else newest_xplane(a.trace)
    raw = inside.read_inside(path)
    steps = a.steps or inside.reduce_inside(raw)["steps"]
    print(render(reduce_mla(raw, steps)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
