"""A stack of single sublayers (Nemotron-H: Mamba-2 `M`, attention `*`,
experts `E`, one a layer) in a trace and on paper: device self time under
the two scopes the dialect adds to the program's vocabulary (`hetu_moe_act`,
the relu^2 of ungated experts on the held rows between the two grouped
matmuls, INSIDE `hetu_moe_experts`; `hetu_ssm_gate_norm`, the gated norm by
group, INSIDE `hetu_ssm_gate`: written in `transformer._routed_experts` and
`_mamba_gate_norm`), by step phase; the rows a held expert takes, from the
program's own pick counter; and the operations a token REQUIRES, by the
letters of `hybrid_override_pattern`.

An op's scope here is the INNERMOST segment of its `op_name` path that is one
of the two, as `reduce/swa.py` reads its own. Reads `inside.read_inside`'s
ops through `inside._reduce_chip` (self times, phases) and edits nothing. A
program that lacks the scopes (any other model; the parent of the PR that
added them) reads as "nothing": every function returns None and does not
raise.

`python -m benchmark.reduce.nemotron_h <trace dir>` prints the table.
"""
import functools
import os
import sys
import traceback

from . import block, inside, lfm2, ssm
from .trace import newest_xplane

# a copy of the program's vocabulary, as in inside.py
ACT, GATE_NORM = SCOPES = ("hetu_moe_act", "hetu_ssm_gate_norm")
PHASES = ("fwd", "recompute", "bwd")


# -- on paper -------------------------------------------------------------------

def letters_of(config):
    return config["hybrid_override_pattern"][:config["num_hidden_layers"]]


def forward_flops_by_letter(config, seq):
    """Forward FLOPs a TOKEN of ONE layer of each letter, and of the head
    ("head"), from config.json and the sequence length; the matmuls' 2 a
    multiply-add, elementwise work not counted:

    M: in-projection 2 D (2 inner + 2 G N + H), the K-tap convolution 2 K
    (inner + 2 G N), the scan (`ssm.ssd_required_flops` a token: its in-chunk
    products at their causal half), out-projection 2 inner D;
    *: q and o 2 * 2 D (heads d), k and v 2 * 2 D (kv heads d), causal scores
    and values at the half they require, 2 * 2 T (heads d) / 2;
    E: the router 2 D (routed experts); the picks HELD HERE at the even share,
    picks a token x held / routed, each 2 * 2 D F_e (two matrices an expert,
    no gate); the shared expert 2 * 2 D F_s on every token;
    head: 2 D V over the vocabulary slice held."""
    c = config
    D, T = c["hidden_size"], seq
    H, P, N, G = (c["mamba_num_heads"], c["mamba_head_dim"],
                  c["ssm_state_size"], c["n_groups"])
    inner = H * P
    heads, kv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                    c["head_dim"])
    routed = c.get("num_routed_experts", c["n_routed_experts"])
    held_picks = c["num_experts_per_tok"] * c["n_routed_experts"] / routed
    return {
        "M": (2 * D * (2 * inner + 2 * G * N + H)
              + 2 * c["conv_kernel"] * (inner + 2 * G * N)
              + ssm.ssd_required_flops(1, T, H, P, N, c["chunk_size"], G)
              / 3.0 / T
              + 2 * inner * D),
        "*": 4 * D * heads * d + 4 * D * kv * d + 2 * T * heads * d,
        "E": (2 * D * routed
              + held_picks * 4 * D * c["moe_intermediate_size"]
              + 4 * D * c.get("moe_shared_expert_intermediate_size", 0)),
        "head": 2 * D * c["vocab_size"]}


def flops_per_token(config, seq):
    """TRAINING FLOPs a token of the cut config.json describes, forward plus
    backward = 3 x forward; recomputation not counted."""
    by = forward_flops_by_letter(config, seq)
    return 3.0 * (sum(by[x] for x in letters_of(config)) + by["head"])


# -- the program's counter ------------------------------------------------------

def rows_per_held_expert(run):
    """Mean rows (picks) a HELD expert takes a layer a step over the traced
    steps, from the program's own counter (`lfm2.held_picks`); None without
    it."""
    counted = lfm2.held_picks(run)
    if not counted:
        return None
    held, _ = counted          # a layer, summed over the traced steps
    steps = len(lfm2.traced_picks(run))
    return sum(held) / (len(held) * steps * run["cell"].config["num_experts"])


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
    where no op carries one of the two scopes."""
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


def scope_ms(run, scope):
    """Device self time a step under `scope`, all phases; None without."""
    r = for_run(run)
    if not r:
        return None
    return sum(r["scope_ms_per_step"][scope].values()) or None


def render(r):
    if not r:
        return "no hetu_moe_act / hetu_ssm_gate_norm scope in this trace"
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
    p = argparse.ArgumentParser(prog="python -m benchmark.reduce.nemotron_h")
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
