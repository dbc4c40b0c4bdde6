"""A looped decoder's exit head in a trace and on paper: device self time
under the program's `hetu_exit` scope (hetu_tpu/telemetry/tracing.py;
written inside `transformer.exit_loss_terms` around the exit gate, the
n_loops head passes, the exit distribution and its entropy), by step phase
and by instruction, and the operations a looped model's step requires from
its shapes alone.

Reads `inside.read_inside`'s ops and their `op_name` paths through
`inside._reduce_chip` (self times, phases) and edits nothing. A program
that lacks the scope (any model with one exit; the parent of the PR that
added it) reads as "nothing": every function returns None and does not
raise. The passes of the trunk are ONE scan body run n_loops times, so a
trace cannot tell pass 1 from pass 4: the trunk's time stays with
`fwd/recompute/bwd_ms_per_step`, all passes together.

`python -m benchmark.reduce.loop <trace dir>` prints the table.
"""
import functools
import os
import sys
import traceback

from . import inside
from .trace import family, newest_xplane

# a copy of the program's vocabulary, as in inside.py
SCOPE = "hetu_exit"
PHASES = ("fwd", "recompute", "bwd")


# -- on paper -------------------------------------------------------------------

def looped_train_flops_per_token(hidden, layers, intermediate, vocab, seq,
                                 loops):
    """Training FLOPs per token of a looped SwiGLU decoder (Ouro), forward
    plus backward = 3 x forward; recomputation not counted.

    per block application, per token, forward: q, k, v and output
    projections 4 * 2*D*D; gate, up and down 3 * 2*D*F; causal attention
    scores and values at the half it requires, 2 * 2*T*D / 2. `layers` x
    `loops` applications. The untied head 2*D*V once an exit: `loops` passes.
    The exit gate (2*D an exit) is below a millionth and left out."""
    D, F, T = hidden, intermediate, seq
    block = 8 * D * D + 6 * D * F + 2 * T * D
    return 3.0 * loops * (layers * block + 2 * D * vocab)


# -- in a trace -----------------------------------------------------------------

def in_scope(op_name):
    return f"/{SCOPE}/" in op_name or op_name.endswith("/" + SCOPE)


def reduce_loop(raw, steps):
    """{"steps", "device_self_ms_per_step", "exit_ms_per_step": {phase: ms},
    "exit_total_ms_per_step", "time_pct", "instructions": [{"family",
    "phase", "calls_per_step", "ms_per_step"}] longest first} from
    `inside.read_inside`'s form, mean over chips; None where no op carries
    the scope."""
    chips = raw["chips"]
    steps, n = max(int(steps), 1), max(len(chips), 1)
    phase_ns = dict.fromkeys(PHASES, 0.0)
    rows = {}
    self_ns = 0.0
    for chip in chips:
        insts, _busy = inside._reduce_chip(chip["ops"])
        for r in insts.values():
            self_ns += r["self_ns"]
            if r["phase"] not in PHASES or not in_scope(r["op_name"]):
                continue
            phase_ns[r["phase"]] += r["self_ns"]
            row = rows.setdefault((family(r["name"], r["kind"]), r["phase"]),
                                  [0, 0.0])
            row[0] += r["calls"]
            row[1] += r["self_ns"]
    exit_ns = sum(phase_ns.values())
    if not exit_ns:
        return None
    per_step = 1e6 * n * steps
    return {
        "steps": steps,
        "device_self_ms_per_step": self_ns / per_step,
        "exit_ms_per_step": {p: ns / per_step for p, ns in phase_ns.items()},
        "exit_total_ms_per_step": exit_ns / per_step,
        "time_pct": 100.0 * exit_ns / self_ns,
        "instructions": [
            {"family": fam, "phase": phase, "calls_per_step": c / n / steps,
             "ms_per_step": ns / per_step}
            for (fam, phase), (c, ns) in sorted(
                rows.items(), key=lambda kv: -kv[1][1])],
    }


@functools.lru_cache(maxsize=4)
def _reduced(path, steps):
    return reduce_loop(inside.read_inside(path), steps)


def for_run(run):
    """The reduced exit-head table of a traced run's own trace, or None (an
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


def render(r, top=12):
    if not r:
        return f"no {SCOPE} scope in this trace"
    by = r["exit_ms_per_step"]
    lines = [f"{r['steps']} traced step(s); exit head "
             f"{r['exit_total_ms_per_step']:.3f} ms of "
             f"{r['device_self_ms_per_step']:.3f} ms device self time a "
             f"step = {r['time_pct']:.1f} % ("
             + ", ".join(f"{p} {by[p]:.3f}" for p in PHASES) + ")",
             "instruction                      phase      calls    ms/step"]
    for row in r["instructions"][:top]:
        lines.append(f"  {row['family']:<30} {row['phase']:<9}"
                     f"{row['calls_per_step']:>7.1f}{row['ms_per_step']:>11.3f}")
    return "\n".join(lines)


def main(argv):
    import argparse
    p = argparse.ArgumentParser(prog="python -m benchmark.reduce.loop")
    p.add_argument("trace", help="a trace dir or one .xplane.pb")
    p.add_argument("--steps", type=int, default=None)
    a = p.parse_args(argv)
    path = a.trace if os.path.isfile(a.trace) else newest_xplane(a.trace)
    raw = inside.read_inside(path)
    steps = a.steps or inside.reduce_inside(raw)["steps"]
    print(render(reduce_loop(raw, steps)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
