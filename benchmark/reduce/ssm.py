"""A Mamba-2 mixer in a trace and on paper: device self time under the
program's four `hetu_ssm_*` scopes (hetu_tpu/telemetry/tracing.py; written
inside `transformer._mamba`: both projections, the causal convolution, the
chunked scan, the gate and its norm), by step phase, and the operations and
bytes the scan and the whole hybrid step require from their shapes alone.

Reads `inside.read_inside`'s ops and their `op_name` paths through
`inside._reduce_chip` (self times, phases) and edits nothing. A program
that lacks the scopes (any model without mamba layers; the parent of the PR
that added them) reads as "nothing": every function returns None and does
not raise. An op the compiler put in with no `op_name` (a copy, a convert)
is in no scope and is not counted here.

`python -m benchmark.reduce.ssm <trace dir>` prints the table.
"""
import functools
import os
import sys
import traceback

from . import inside
from .trace import family, newest_xplane

# a copy of the program's vocabulary, as in inside.py
PROJ, CONV, SCAN, GATE = SCOPES = ("hetu_ssm_proj", "hetu_ssm_conv",
                                   "hetu_ssm_scan", "hetu_ssm_gate")
PHASES = ("fwd", "recompute", "bwd")


# -- on paper -------------------------------------------------------------------

def ssd_required_flops(batch, seq, heads, head_dim, state, chunk, groups=1):
    """Training FLOPs of one chunked scan (transformer._ssd), forward plus
    backward = 3 x forward; recomputation not counted. Forward, with Q =
    chunk and c = seq / Q chunks a sequence:

    inside a chunk, the masked products at the causal half they require, Q
    (Q + 1) / 2 pairs of positions: C B^T, 2 * state a pair and group, and
    its product with x dt, 2 * head_dim a pair and head;
    the chunks' states, 2 * head_dim * state a position and head;
    the entering state's part C S, the same again.
    The elementwise work (exp, the decay matrix, the 32-step recurrence over
    chunk states) is not counted: it runs on the VPU, not the MXU."""
    pairs = chunk * (chunk + 1) // 2
    inside_chunks = batch * (seq // chunk) * pairs * 2.0 * (
        groups * state + heads * head_dim)
    states = batch * seq * heads * 2.0 * head_dim * state
    return 3.0 * (inside_chunks + 2 * states)


def ssd_required_bytes(batch, seq, heads, head_dim, state, chunk, groups=1,
                       itemsize=2):
    """Bytes one scan must move, forward plus backward = 3 x forward (the
    backward pass reads the forward's operands and the output's cotangent
    and writes every operand's): forward reads x (heads * head_dim a
    position), B and C (groups * state each) at the compute dtype and dt
    (float32 a head), and writes y like x. `chunk` moves nothing: the decay
    matrix and the chunk states are made and used on the chip."""
    del chunk
    a_position = (itemsize * (2 * heads * head_dim + 2 * groups * state)
                  + 4 * heads)
    return 3.0 * batch * seq * a_position


def granite_train_flops_per_token(config, seq):
    """Training FLOPs per token of a Granite 4.0-H style hybrid decoder
    from its config.json, forward plus backward = 3 x forward; recomputation
    not counted. Per token, forward:

    every layer: the SwiGLU MLP 3 * 2*D*F;
    a mamba layer: in-projection 2*D*(2*inner + 2*G*N + H), the width-K
    convolution 2*K*(inner + 2*G*N), the scan (`ssd_required_flops` a
    token), out-projection 2*inner*D;
    an attention layer: q and o 2 * 2*D*D, k and v 2 * 2*D*kv_width, causal
    scores and values at the half they require, 2 * 2*T*D / 2;
    the tied head 2*D*V once."""
    c = config
    D, F, T = c["hidden_size"], c["shared_intermediate_size"], seq
    H, P, N, G = (c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"],
                  c["mamba_n_groups"])
    inner = H * P
    kinds = c["layer_types"][:c["num_hidden_layers"]]
    mamba = (2 * D * (2 * inner + 2 * G * N + H)
             + 2 * c["mamba_d_conv"] * (inner + 2 * G * N)
             + ssd_required_flops(1, T, H, P, N, c["mamba_chunk_size"], G)
             / 3.0 / T
             + 2 * inner * D)
    kv_width = D // c["num_attention_heads"] * c["num_key_value_heads"]
    attention = 4 * D * D + 4 * D * kv_width + 2 * T * D
    return 3.0 * (len(kinds) * 6 * D * F
                  + kinds.count("mamba") * mamba
                  + kinds.count("attention") * attention
                  + 2 * D * c["vocab_size"])


# -- in a trace -----------------------------------------------------------------

def scope_of(op_name):
    for scope in SCOPES:
        if f"/{scope}/" in op_name or op_name.endswith("/" + scope):
            return scope
    return None


def reduce_ssm(raw, steps):
    """{"steps", "device_self_ms_per_step", "scope_ms_per_step": {scope:
    {phase: ms}}, "ssm_ms_per_step", "time_pct", "instructions": [{"scope",
    "family", "phase", "calls_per_step", "ms_per_step"}] longest first} from
    `inside.read_inside`'s form, mean over chips; None where no op carries a
    scope."""
    chips = raw["chips"]
    steps, n = max(int(steps), 1), max(len(chips), 1)
    scope_ns = {s: dict.fromkeys(PHASES, 0.0) for s in SCOPES}
    rows = {}
    self_ns = ssm_ns = 0.0
    for chip in chips:
        insts, _busy = inside._reduce_chip(chip["ops"])
        for r in insts.values():
            self_ns += r["self_ns"]
            scope = scope_of(r["op_name"])
            if scope is None or r["phase"] not in PHASES:
                continue
            ssm_ns += r["self_ns"]
            scope_ns[scope][r["phase"]] += r["self_ns"]
            row = rows.setdefault(
                (scope, family(r["name"], r["kind"]), r["phase"]), [0, 0.0])
            row[0] += r["calls"]
            row[1] += r["self_ns"]
    if not ssm_ns:
        return None
    per_step = 1e6 * n * steps
    return {
        "steps": steps,
        "device_self_ms_per_step": self_ns / per_step,
        "scope_ms_per_step": {s: {p: ns / per_step for p, ns in by.items()}
                              for s, by in scope_ns.items()},
        "ssm_ms_per_step": ssm_ns / per_step,
        "time_pct": 100.0 * ssm_ns / self_ns,
        "instructions": [
            {"scope": scope, "family": fam, "phase": phase,
             "calls_per_step": c / n / steps, "ms_per_step": ns / per_step}
            for (scope, fam, phase), (c, ns) in sorted(
                rows.items(), key=lambda kv: -kv[1][1])],
    }


@functools.lru_cache(maxsize=4)
def _reduced(path, steps):
    return reduce_ssm(inside.read_inside(path), steps)


def for_run(run):
    """The reduced mixer table of a traced run's own trace, or None (an
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


def scan_roofline_pct(scan_ms_per_step, config, traffic, device_kind):
    """The least time the chip could take for the step's scans (every mamba
    layer's, forward and backward: the larger of required FLOPs over peak
    FLOP/s and required bytes over peak bytes/s) over the time measured
    under `hetu_ssm_scan`, in percent."""
    from . import peaks
    c = config
    shape = (traffic["sequences"], traffic["seq_len"], c["mamba_n_heads"],
             c["mamba_d_head"], c["mamba_d_state"], c["mamba_chunk_size"],
             c["mamba_n_groups"])
    layers = c["layer_types"][:c["num_hidden_layers"]].count("mamba")
    peak = peaks.peaks(device_kind)
    least_s = layers * max(
        ssd_required_flops(*shape) / (peak["tflops"] * 1e12),
        ssd_required_bytes(*shape) / (peak["gbs"] * 1e9))
    return 100.0 * least_s / (scan_ms_per_step / 1e3)


def render(r, top=14):
    if not r:
        return "no hetu_ssm_* scope in this trace"
    lines = [f"{r['steps']} traced step(s); Mamba-2 mixers "
             f"{r['ssm_ms_per_step']:.3f} ms of "
             f"{r['device_self_ms_per_step']:.3f} ms device self time a "
             f"step = {r['time_pct']:.1f} %",
             "scope                  fwd  recompute       bwd     total"]
    for s in SCOPES:
        by = r["scope_ms_per_step"][s]
        lines.append(f"  {s:<18}" + "".join(f"{by[p]:>10.3f}" for p in PHASES)
                     + f"{sum(by.values()):>10.3f}")
    lines.append("scope           instruction                      phase   "
                 "   calls    ms/step")
    for row in r["instructions"][:top]:
        lines.append(f"  {row['scope']:<14}{row['family']:<33}"
                     f"{row['phase']:<9}{row['calls_per_step']:>7.1f}"
                     f"{row['ms_per_step']:>11.3f}")
    return "\n".join(lines)


def main(argv):
    import argparse
    p = argparse.ArgumentParser(prog="python -m benchmark.reduce.ssm")
    p.add_argument("trace", help="a trace dir or one .xplane.pb")
    p.add_argument("--steps", type=int, default=None)
    a = p.parse_args(argv)
    path = a.trace if os.path.isfile(a.trace) else newest_xplane(a.trace)
    raw = inside.read_inside(path)
    steps = a.steps or inside.reduce_inside(raw)["steps"]
    print(render(reduce_ssm(raw, steps)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
