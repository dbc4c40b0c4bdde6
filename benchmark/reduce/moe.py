"""The mixture-of-experts block in a trace and on paper: device self time
under the program's four `hetu_moe_*` scopes (hetu_tpu/telemetry/
tracing.py; written inside `transformer._moe_mlp`), by step phase, the
grouped matmuls among them, and the operations and bytes the block requires
from its shapes alone.

Reads `inside.read_inside`'s ops and their `op_name` paths through
`inside._reduce_chip` (self times, phases) and edits nothing. A program
that lacks the scopes (any dense model; the parent of the PR that added
them) reads as "nothing": every function returns None or an empty table
and does not raise. An op the compiler put in with no `op_name` (a copy, a
convert) is in no scope and is not counted here; the grouped matmuls, whose
path the compiler overwrites, are found by name (`is_grouped_matmul`).

`python -m benchmark.reduce.moe <trace dir>` prints the table.
"""
import functools
import os
import re
import sys
import traceback

from . import inside
from .trace import MOSAIC, family, newest_xplane

# a copy of the program's vocabulary, as in inside.py
SCOPES = ("hetu_moe_route", "hetu_moe_dispatch", "hetu_moe_experts",
          "hetu_moe_combine")
EXPERTS = "hetu_moe_experts"
PHASES = ("fwd", "recompute", "bwd")


# -- on paper -------------------------------------------------------------------

def moe_expert_matmul_flops(picks, d_model, d_ff):
    """One grouped matmul over `picks` rows: gate, up (d_model -> d_ff) and
    down (d_ff -> d_model) are each 2 * picks * d_model * d_ff, and so is
    each of the two backward products of each. A SwiGLU block runs three
    forward; training requires three times that, and a forward run again
    under `remat` is counted as run, because the kernel ran."""
    return 2.0 * picks * d_model * d_ff


def moe_expert_matmul_bytes(picks, d_model, d_ff, n_experts, itemsize=2):
    """Bytes one such call must move at the compute dtype: the rows in, all
    experts' matrices once, the rows out."""
    return float(itemsize) * (picks * d_model + n_experts * d_model * d_ff
                              + picks * d_ff)


def moe_train_flops_per_token(d_model, d_ff, n_experts, per_tok):
    """Training FLOPs a token of one MoE block: the picks' three
    projections and the router, times three for forward plus backward."""
    return 3.0 * (per_tok * 3 * 2 * d_model * d_ff + 2 * d_model * n_experts)


def olmoe_train_flops_per_token(hidden, layers, expert_width, n_experts,
                                per_tok, vocab, seq):
    """Training FLOPs per token of an OLMoE-style causal LM, forward plus
    backward = 3 x forward; recomputation not counted.

    per layer, per token, forward: q, k, v and output projections 4 * 2*D*D;
    causal attention scores and values at the half it requires,
    2 * 2*T*D / 2; the MoE block (above). Untied head 2*D*V."""
    D, T = hidden, seq
    layer = 8 * D * D + 2 * T * D
    return (3.0 * (layers * layer + 2 * D * vocab)
            + layers * moe_train_flops_per_token(D, expert_width, n_experts,
                                                 per_tok))


# -- in a trace -----------------------------------------------------------------

def scope_of(op_name):
    for scope in SCOPES:
        if f"/{scope}/" in op_name or op_name.endswith("/" + scope):
            return scope
    return None


def is_grouped_matmul(record):
    """A grouped matmul of the experts. `jax.lax.ragged_dot` becomes the
    compiler's own `tpu_custom_call`, which it names `ragged-dot-none.N` and
    whose `op_name` it overwrites with `ragged-dot-none:` (settled on a v5e
    trace, PR 25): the program's scope is lost, so the instruction's name is
    what finds it. (`ragged-dot-metadata` computes the tiles' offsets.) A
    Pallas kernel under `hetu_moe_experts`, should the program grow one,
    keeps its path and is found by it."""
    if record["kind"] != MOSAIC:
        return False
    return (scope_of(record["op_name"]) == EXPERTS
            or family(record["name"]).startswith("ragged-dot-none"))


def _consumer_phase(insts, name):
    """The phase of the longest-running op that reads `name`'s result (the
    rule inside.py applies to an op without a path); a result the optimizer
    reads is a gradient, so backward work. Else fwd."""
    best = None
    for r in insts.values():
        if r["phase"] not in PHASES + ("opt",):
            continue
        operands = r["text"].split(" = ", 1)[-1]
        if re.search(rf"%{re.escape(name)}\b", operands) and (
                best is None or r["self_ns"] > best["self_ns"]):
            best = r
    if best is None:
        return "fwd"
    return "bwd" if best["phase"] == "opt" else best["phase"]


def reduce_moe(raw, steps):
    """{"steps", "device_self_ms_per_step", "scope_ms_per_step": {scope:
    {phase: ms}}, "moe_ms_per_step", "time_pct", "grouped_matmul":
    {"calls_per_step", "ms_per_step", "ms_per_call"}} from
    `inside.read_inside`'s form, mean over chips; None where no op carries
    a scope. The grouped matmuls count under `hetu_moe_experts`."""
    chips = raw["chips"]
    steps, n = max(int(steps), 1), max(len(chips), 1)
    scope_ns = {s: dict.fromkeys(PHASES, 0.0) for s in SCOPES}
    self_ns = gmm_ns = scoped_ns = 0.0
    gmm_calls = 0
    for chip in chips:
        insts, _busy = inside._reduce_chip(chip["ops"])
        for r in insts.values():
            self_ns += r["self_ns"]
            if is_grouped_matmul(r):
                scope = EXPERTS
                phase = (r["phase"] if scope_of(r["op_name"])
                         else _consumer_phase(insts, r["name"]))
                gmm_ns += r["self_ns"]
                gmm_calls += r["calls"]
            else:
                scope, phase = scope_of(r["op_name"]), r["phase"]
                if scope is None or phase not in PHASES:
                    continue
                scoped_ns += r["self_ns"]
            scope_ns[scope][phase] += r["self_ns"]
    if not scoped_ns:
        return None
    moe_ns = scoped_ns + gmm_ns
    per_step = 1e6 * n * steps
    return {
        "steps": steps,
        "device_self_ms_per_step": self_ns / per_step,
        "scope_ms_per_step": {s: {p: ns / per_step for p, ns in by.items()}
                              for s, by in scope_ns.items()},
        "moe_ms_per_step": moe_ns / per_step,
        "time_pct": 100.0 * moe_ns / self_ns,
        "grouped_matmul": {
            "calls_per_step": gmm_calls / n / steps,
            "ms_per_step": gmm_ns / per_step,
            "ms_per_call": gmm_ns / 1e6 / gmm_calls if gmm_calls else 0.0},
    }


@functools.lru_cache(maxsize=4)
def _reduced(path, steps):
    return reduce_moe(inside.read_inside(path), steps)


def for_run(run):
    """The reduced MoE table of a traced run's own trace, or None (an
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


def render(r):
    if not r:
        return "no hetu_moe_* scope in this trace"
    lines = [f"{r['steps']} traced step(s); MoE block "
             f"{r['moe_ms_per_step']:.3f} ms of "
             f"{r['device_self_ms_per_step']:.3f} ms device self time a "
             f"step = {r['time_pct']:.1f} %",
             "scope                  fwd  recompute       bwd     total"]
    for s in SCOPES:
        by = r["scope_ms_per_step"][s]
        lines.append(f"  {s:<18}" + "".join(f"{by[p]:>10.3f}" for p in PHASES)
                     + f"{sum(by.values()):>10.3f}")
    g = r["grouped_matmul"]
    lines.append(f"grouped matmuls: {g['calls_per_step']:.1f} calls a step, "
                 f"{g['ms_per_call']:.3f} ms a call, {g['ms_per_step']:.3f} "
                 "ms a step")
    return "\n".join(lines)


def main(argv):
    import argparse
    p = argparse.ArgumentParser(prog="python -m benchmark.reduce.moe")
    p.add_argument("trace", help="a trace dir or one .xplane.pb")
    p.add_argument("--steps", type=int, default=None)
    a = p.parse_args(argv)
    path = a.trace if os.path.isfile(a.trace) else newest_xplane(a.trace)
    raw = inside.read_inside(path)
    steps = a.steps or inside.reduce_inside(raw)["steps"]
    print(render(reduce_moe(raw, steps)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
