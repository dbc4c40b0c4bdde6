"""Device self time under a tuple of the program's `jax.named_scope`s in a
trace, by step phase: the one reader that a mixer's file (`reduce/gdn.py`)
hands its scopes. An op's scope is the INNERMOST segment of its `op_name`
path that is one of the tuple. Reads `inside.read_inside`'s ops through
`inside._reduce_chip` (self times, phases) and edits nothing. A program that
wrote none of the scopes reads as "nothing": every function returns None and
does not raise.
"""
import functools
import os
import traceback

from . import block, inside
from .trace import newest_xplane

PHASES = ("fwd", "recompute", "bwd")


def scope_of(scopes, op_name):
    """The innermost segment of an `op_name` path that is one of `scopes`."""
    for segment in reversed(op_name.split("/")):
        m = block._WRAPPED.match(segment)
        if m and m.group(1) in scopes:
            return m.group(1)
    return None


def reduce_scopes(scopes, raw, steps):
    """{"steps", "device_self_ms_per_step", "scope_ms_per_step": {scope:
    {phase: ms}}} from `inside.read_inside`'s form, mean over chips; None
    where no op carries one of `scopes`."""
    chips = raw["chips"]
    steps, n = max(int(steps), 1), max(len(chips), 1)
    scope_ns = {s: dict.fromkeys(PHASES, 0.0) for s in scopes}
    self_ns = found_ns = 0.0
    for chip in chips:
        insts, _busy = inside._reduce_chip(chip["ops"])
        for r in insts.values():
            self_ns += r["self_ns"]
            scope = scope_of(scopes, r["op_name"])
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
def _reduced(scopes, path, steps):
    return reduce_scopes(scopes, inside.read_inside(path), steps)


def for_run(scopes, run):
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
        return _reduced(tuple(scopes), path, t.get("steps") or 1)
    except Exception:       # noqa: BLE001 - a reader returns nothing instead
        traceback.print_exc()
        return None


def scope_ms(r, *which):
    """Device self time a step under the scopes `which` of `for_run`'s table
    (each op counted under its innermost one), all phases; None without."""
    if not r:
        return None
    return sum(sum(r["scope_ms_per_step"][s].values()) for s in which) or None


def time_pct(r):
    """The scopes' share of the device self time a step, in %, from
    `for_run`'s table."""
    if not r or not r["device_self_ms_per_step"]:
        return None
    return 100.0 * sum(sum(by.values()) for by in r[
        "scope_ms_per_step"].values()) / r["device_self_ms_per_step"]


def render(scopes, r):
    if not r:
        return f"no {os.path.commonprefix(list(scopes))}* scope in this trace"
    lines = [f"{r['steps']} traced step(s); "
             f"{r['device_self_ms_per_step']:.3f} ms device self time a step",
             "scope                      fwd  recompute       bwd     total"]
    for s in scopes:
        by = r["scope_ms_per_step"][s]
        lines.append(f"  {s:<22}" + "".join(f"{by[p]:>10.3f}" for p in PHASES)
                     + f"{sum(by.values()):>10.3f}")
    return "\n".join(lines)


def main(scopes, prog, argv):
    """`python -m benchmark.reduce.<mixer> <trace dir>`: print the table."""
    import argparse
    p = argparse.ArgumentParser(prog=prog)
    p.add_argument("trace", help="a trace dir or one .xplane.pb")
    p.add_argument("--steps", type=int, default=None)
    a = p.parse_args(argv)
    path = a.trace if os.path.isfile(a.trace) else newest_xplane(a.trace)
    raw = inside.read_inside(path)
    steps = a.steps or inside.reduce_inside(raw)["steps"]
    print(render(scopes, reduce_scopes(scopes, raw, steps)))
    return 0
