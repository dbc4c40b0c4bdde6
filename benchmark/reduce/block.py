"""The parts of the flagship step in a trace: device self time by (scope,
phase) a step, over the whole of the program's scope vocabulary
(hetu_tpu/telemetry/tracing.py): the ordinary parts of a block
(`hetu_blk_*`: projections, attention, the two halves of the MLP, norms),
`hetu_embed` and `hetu_head` outside it, the three parts of the chunked scan
(`hetu_ssd_*`, inside `hetu_ssm_scan`), and the older scopes a model PR
brought (`hetu_moe_*`, `hetu_exit`, `hetu_ssm_*`) with `hetu_opt`. What is
under `hetu_fwd` alone (residual adds, the scans' own bookkeeping, what the
compiler put between the parts) is `rest`.

An op's scope is the INNERMOST vocabulary SEGMENT of its `op_name` path: the
path is split on `/`, the `jvp(` / `transpose(` wrappers are peeled off a
segment, and the last segment that is a name of the vocabulary wins
(`.../hetu_exit/hetu_head/fused_ce_fwd/pallas_call` is the head's,
`.../hetu_ssm_scan/hetu_ssd_inchunk/...` the in-chunk part's; no substring
test: `hetu_blk_attn` and the checkpoint name `hetu_attn_o` never meet). Its
phase is `inside.phase_of`'s. An op with no path (the compiler's copies and
converts) takes the scope of the op that consumes its result and runs
longest, as `inside.py` does for phases. The experts' grouped matmuls, whose
path the compiler overwrites, count under `hetu_moe_experts` as in
`reduce/moe.py`.

The known limit: the compiler fuses ACROSS scopes (on BERT `w1` + GELU +
`w2` + the residual add + the LayerNorm statistic are one forward fusion),
and a fusion carries ONE `op_name`, that of its root. THE FUSION COUNTS UNDER
THE SCOPE ITS OWN `op_name` GIVES; what else it holds is that metric's error
bar (PERF.md lists every such fusion of 1 ms a step or more).

Reads `inside.read_inside`'s ops through `inside._reduce_chip` and edits
nothing. A program that lacks the new names (the parent of the PR that
added them, the graph executor's step) reads as "nothing": every function
returns None and does not raise.

`python -m benchmark.reduce.block <trace dir>` prints the table.
"""
import functools
import os
import re
import sys
import traceback

from . import inside, moe
from .trace import family, newest_xplane

# a copy of the program's vocabulary, as in inside.py
QKV, ATTN, WO, MLP_UP, MLP_DOWN, NORM = BLOCK = (
    "hetu_blk_qkv", "hetu_blk_attn", "hetu_blk_wo", "hetu_blk_mlp_up",
    "hetu_blk_mlp_down", "hetu_blk_norm")
EMBED, HEAD = "hetu_embed", "hetu_head"
SSD_INCHUNK, SSD_STATES, SSD_ENTER = SSD = (
    "hetu_ssd_inchunk", "hetu_ssd_states", "hetu_ssd_enter")
NEW = BLOCK + (EMBED, HEAD) + SSD           # what this file was written for
OLDER = moe.SCOPES + ("hetu_exit", "hetu_ssm_proj", "hetu_ssm_conv",
                      "hetu_ssm_scan", "hetu_ssm_gate", inside.SCOPE_OPT)
SCOPES = NEW + OLDER
REST = "rest"
STEP_PHASES = ("fwd", "recompute", "bwd")   # what a block scope can be in
PHASES = inside.PHASES + (inside.UNATTRIBUTED,)
_VOCABULARY = frozenset(SCOPES)
_WRAPPED = re.compile(r"^(?:\w+\()*([^()]*)\)*$")


def scope_of(op_name):
    """The innermost vocabulary segment of an `op_name` path, None where it
    has none (an empty path too)."""
    for segment in reversed(op_name.split("/")):
        m = _WRAPPED.match(segment)
        if m and m.group(1) in _VOCABULARY:
            return m.group(1)
    return None


def _scopes(insts):
    """{instruction name: scope or REST} of one chip's instructions: by
    path; the grouped matmuls by name; an op without a path by its
    longest-running consumer (three rounds, as `inside._reduce_chip`)."""
    out = {}
    for name, r in insts.items():
        if moe.is_grouped_matmul(r):
            out[name] = moe.EXPERTS
        elif r["op_name"]:
            out[name] = scope_of(r["op_name"]) or REST
    for _round in range(3):
        orphans = set(insts) - set(out)
        if not orphans:
            break
        best = {}
        for name, r in insts.items():
            if name not in out:
                continue
            operands = r["text"].split(" = ", 1)[-1]
            for used in set(inside._OPERAND.findall(operands)) & orphans:
                if used not in best or r["self_ns"] > best[used]["self_ns"]:
                    best[used] = r
        if not best:
            break
        for name, consumer in best.items():
            out[name] = out[consumer["name"]]
    return out


def reduce_block(raw, steps):
    """{"steps", "device_self_ms_per_step", "scope_ms_per_step": {scope or
    "rest": {phase: ms}}, "named_pct", "instructions": [{"scope", "family",
    "phase", "calls_per_step", "ms_per_step"}] longest first} from
    `inside.read_inside`'s form, mean over chips; None where no op carries
    one of the NEW scopes. `named_pct` is the share of device self time under
    any scope of the vocabulary (so not under `hetu_fwd` alone) or in a
    collective."""
    chips = raw["chips"]
    steps, n = max(int(steps), 1), max(len(chips), 1)
    scope_ns = {s: dict.fromkeys(PHASES, 0.0) for s in SCOPES + (REST,)}
    rows = {}
    self_ns = new_ns = 0.0
    for chip in chips:
        insts, _busy = inside._reduce_chip(chip["ops"])
        scopes = _scopes(insts)
        for name, r in insts.items():
            scope = scopes.get(name, REST)
            phase = r["phase"] or inside.UNATTRIBUTED
            if scope == moe.EXPERTS and not scope_of(r["op_name"]):
                phase = moe._consumer_phase(insts, name)
            self_ns += r["self_ns"]
            scope_ns[scope][phase] += r["self_ns"]
            if scope in NEW:
                new_ns += r["self_ns"]
            row = rows.setdefault(
                (scope, family(r["name"], r["kind"]), phase), [0, 0.0])
            row[0] += r["calls"]
            row[1] += r["self_ns"]
    if not new_ns:
        return None
    per_step = 1e6 * n * steps
    unnamed = sum(ns for p, ns in scope_ns[REST].items() if p != "collective")
    return {
        "steps": steps,
        "device_self_ms_per_step": self_ns / per_step,
        "scope_ms_per_step": {s: {p: ns / per_step for p, ns in by.items()}
                              for s, by in scope_ns.items()},
        "named_pct": 100.0 * (1.0 - unnamed / self_ns),
        "instructions": [
            {"scope": scope, "family": fam, "phase": phase,
             "calls_per_step": c / n / steps, "ms_per_step": ns / per_step}
            for (scope, fam, phase), (c, ns) in sorted(
                rows.items(), key=lambda kv: -kv[1][1])],
    }


@functools.lru_cache(maxsize=4)
def _reduced(path, steps):
    return reduce_block(inside.read_inside(path), steps)


def for_run(run):
    """The reduced table of a traced run's own trace, or None (an
    end-to-end run, no trace, none of the names, a trace this file cannot
    read: the reason goes to stderr and the reader leaves its metric out)."""
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


def scope_ms(run, *scopes, phases=STEP_PHASES):
    """Device self time a step under `scopes` in `phases`; None without the
    names. A scope the program did not write reads 0.0 beside one it did."""
    r = for_run(run)
    if not r:
        return None
    return sum(r["scope_ms_per_step"][s][p] for s in scopes for p in phases)


def named_pct(run):
    r = for_run(run)
    return r["named_pct"] if r else None


def render(r, top=16):
    if not r:
        return "no hetu_blk_* / hetu_embed / hetu_head scope in this trace"
    whole = r["device_self_ms_per_step"]
    lines = [f"{r['steps']} traced step(s); device self time {whole:.3f} ms "
             f"a step, {r['named_pct']:.1f} % of it under a scope of the "
             "vocabulary or in a collective",
             "scope                     fwd  recompute       bwd     other"
             "     total   share"]
    for s in SCOPES + (REST,):
        by = r["scope_ms_per_step"][s]
        total = sum(by.values())
        if not total:
            continue
        step = [by[p] for p in STEP_PHASES]
        lines.append(f"  {s:<20}" + "".join(f"{ms:>10.3f}" for ms in step)
                     + f"{total - sum(step):>10.3f}{total:>10.3f}"
                     f"{100.0 * total / whole:>7.1f} %")
    step = [sum(by[p] for by in r["scope_ms_per_step"].values())
            for p in STEP_PHASES]
    lines.append(f"  {'all':<20}" + "".join(f"{ms:>10.3f}" for ms in step)
                 + f"{whole - sum(step):>10.3f}{whole:>10.3f}")
    lines.append("(other: optimizer, collectives, unattributed)")
    lines.append("scope               instruction                      phase"
                 "      calls    ms/step")
    for row in r["instructions"][:top]:
        lines.append(f"  {row['scope']:<18}{row['family']:<33}"
                     f"{row['phase']:<11}{row['calls_per_step']:>7.1f}"
                     f"{row['ms_per_step']:>11.3f}")
    return "\n".join(lines)


def main(argv):
    import argparse
    p = argparse.ArgumentParser(prog="python -m benchmark.reduce.block")
    p.add_argument("trace", help="a trace dir or one .xplane.pb")
    p.add_argument("--steps", type=int, default=None)
    p.add_argument("--top", type=int, default=16)
    a = p.parse_args(argv)
    path = a.trace if os.path.isfile(a.trace) else newest_xplane(a.trace)
    raw = inside.read_inside(path)
    steps = a.steps or inside.reduce_inside(raw)["steps"]
    print(render(reduce_block(raw, steps), a.top))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
