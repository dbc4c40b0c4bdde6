"""A router AHEAD of attention (SmallThinker: `Router.input` "block") in a
trace and on paper: device self time under the scope the dialect adds to the
program's vocabulary (`hetu_moe_route_early`, AROUND `hetu_moe_route`:
written in `transformer._block` before the mixer), by step phase; whether
the compiler RAN the routing ahead of the layer's attention, from the ops'
own start and end times; and the operations a token REQUIRES of a stack of
NoPE global layers and rotary window layers over ReGLU experts cut to a
share, from config.json's published keys.

"Ahead", for one expert layer of one traced FORWARD pass (phase fwd: not the
forward run again under `remat`, whose order is the backward pass's): the
layer's routing ops (forward ops under `hetu_moe_route_early` that start
after the previous layer's last forward op under `hetu_moe_experts` ended and
before this layer's first one starts: a scan's iterations do not interleave,
and a layer's experts consume its routing) ALL END before the layer's
forward flash kernel STARTS. A layer whose routing the compiler sank behind
attention, wholly or in part, is not ahead. 100 = every layer's routing was
issued ahead, 0 = none.

Reads `inside.read_inside`'s ops (`inside._reduce_chip` for self times and
phases) and edits nothing. A program that lacks the scope (any other model;
the parent of the PR that added it) reads as "nothing": every function
returns None and does not raise.

`python -m benchmark.reduce.smallthinker <trace dir>` prints the table.
"""
import functools
import os
import sys
import traceback

from . import inside
from .dsa import attn_fwd_flops
from .mla import causal_pairs
from .swa import kept_pairs
from .trace import MOSAIC, family, newest_xplane, parse_op

# a copy of the program's vocabulary, as in inside.py
EARLY = "hetu_moe_route_early"
EXPERTS = "hetu_moe_experts"
PHASES = ("fwd", "recompute", "bwd")
FLASH_FWD = "flash_fwd"


# -- on paper -------------------------------------------------------------------

def layouts(config):
    """[(a window layer?, a rotary layer?)] of the first `num_hidden_layers`
    layers."""
    n = config["num_hidden_layers"]
    return list(zip(map(bool, config["sliding_window_layout"][:n]),
                    map(bool, config["rope_layout"][:n])))


def forward_flops(config, seq):
    """The forward pass's REQUIRED operations of one sequence of `seq`
    tokens of a SmallThinker config.json CUT TO A SHARE, by part, the
    matmuls' 2 a multiply-add, elementwise work not counted: the attention
    cores of the global layers over every causal pair and of the window
    layers over the pairs a window KEEPS, 4 d H a pair; attention's
    projections (q and o at H heads, k and v at G); the routers, 2 D x the
    router's width; the picks HELD HERE at the even share (k x held / routed
    a token), each ReGLU expert 3 x 2 D F; the head over the vocabulary
    held."""
    c = config
    D, H, G, d = (c["hidden_size"], c["num_attention_heads"],
                  c["num_key_value_heads"], c["head_dim"])
    kinds = layouts(c)
    window = sum(1 for w, _ in kinds if w)
    held = c["moe_num_primary_experts"]
    routed = c.get("num_routed_experts", held)
    held_picks = c["moe_num_active_primary_experts"] * held / routed
    return {
        "global_core": (len(kinds) - window) * attn_fwd_flops(
            1, H, causal_pairs(seq), d),
        "window_core": window * attn_fwd_flops(
            1, H, kept_pairs(seq, c["sliding_window_size"]), d),
        "attention_proj": seq * len(kinds) * (
            2 * D * (H * d + 2 * G * d) + 2 * H * d * D),
        "router": seq * len(kinds) * 2 * D * routed,
        "experts": seq * len(kinds) * held_picks * 6 * D
        * c["moe_ffn_hidden_size"],
        "head": seq * 2 * D * c["vocab_size"]}


def train_flops_per_token(config, seq):
    """TRAINING FLOPs a token; recomputation not counted. The weights' parts
    forward plus backward = 3 x forward; the cores over the pairs each kind
    KEEPS, forward 4 and backward 10 d H a pair = 3.5 x forward."""
    fwd = forward_flops(config, seq)
    cores = fwd["global_core"] + fwd["window_core"]
    return (3.0 * (sum(fwd.values()) - cores) + 3.5 * cores) / seq


# -- in a trace -----------------------------------------------------------------

def _under(op_name, scope):
    return f"/{scope}/" in op_name or op_name.endswith("/" + scope)


def _is_flash_fwd(text):
    name, kind = parse_op(text)
    return kind == MOSAIC and FLASH_FWD in family(name)


def ahead_of_attention(ops):
    """One chip's `XLA Ops` events [[text, start_ns, dur_ns, op_name], ...]
    -> [{"flash_start", "route_start", "route_end", "route_ops", "route_ns",
    "ahead_ns", "ahead"}] one an expert layer of a traced forward pass that
    has routing ops, in time order (module docstring); "route_ns" the
    routing ops' durations summed and "ahead_ns" the part of them in ops
    that ended before the flash call started."""
    fwd = sorted((e for e in ops
                  if inside.phase_of(*parse_op(e[0]), e[3]) == "fwd"),
                 key=lambda e: e[1])
    flash = [e for e in fwd if _is_flash_fwd(e[0])]
    experts = [e for e in fwd if _under(e[3], EXPERTS)]
    early = [e for e in fwd if _under(e[3], EARLY)]
    layers = []
    for call in flash:
        start, end = call[1], call[1] + call[2]
        lo = max((e[1] + e[2] for e in experts if e[1] + e[2] <= start),
                 default=float("-inf"))
        hi = min((e[1] for e in experts if e[1] >= end),
                 default=float("inf"))
        mine = [e for e in early if lo <= e[1] < hi]
        if not mine:
            continue
        route_end = max(e[1] + e[2] for e in mine)
        layers.append({"flash_start": start,
                       "route_start": min(e[1] for e in mine),
                       "route_end": route_end, "route_ops": len(mine),
                       "route_ns": sum(e[2] for e in mine),
                       "ahead_ns": sum(e[2] for e in mine
                                       if e[1] + e[2] <= start),
                       "ahead": route_end <= start})
    return layers


def reduce_early(raw, steps):
    """{"steps", "device_self_ms_per_step", "early_ms_per_step": {phase:
    ms}, "layers" (expert layers of the traced forward passes with routing
    ops), "ahead" (of them, those whose routing ended before their flash
    call started), "ahead_pct", "ahead_time_pct" (of the routing ops'
    forward time, the part in ops that ended before their layer's flash
    call started: what the compiler left ahead where it sank the rest),
    "lead_us_p50" (median of flash start less routing end over the layers,
    microseconds: negative = behind)} from
    `inside.read_inside`'s form, mean over chips; None where no op carries
    the scope."""
    chips = raw["chips"]
    steps, n = max(int(steps), 1), max(len(chips), 1)
    by_phase = dict.fromkeys(PHASES, 0.0)
    self_ns = found_ns = 0.0
    layers = []
    for chip in chips:
        insts, _busy = inside._reduce_chip(chip["ops"])
        for r in insts.values():
            self_ns += r["self_ns"]
            if _under(r["op_name"], EARLY) and r["phase"] in PHASES:
                found_ns += r["self_ns"]
                by_phase[r["phase"]] += r["self_ns"]
        layers += ahead_of_attention(chip["ops"])
    if not found_ns:
        return None
    per_step = 1e6 * n * steps
    leads = sorted(l["flash_start"] - l["route_end"] for l in layers)
    ahead = sum(l["ahead"] for l in layers)
    return {"steps": steps,
            "device_self_ms_per_step": self_ns / per_step,
            "early_ms_per_step": {p: ns / per_step
                                  for p, ns in by_phase.items()},
            "layers": len(layers), "ahead": ahead,
            "ahead_pct": 100.0 * ahead / len(layers) if layers else None,
            "ahead_time_pct": (100.0 * sum(l["ahead_ns"] for l in layers)
                               / max(sum(l["route_ns"] for l in layers), 1e-9)
                               if layers else None),
            "lead_us_p50": leads[len(leads) // 2] / 1e3 if leads else None}


@functools.lru_cache(maxsize=4)
def _reduced(path, steps):
    return reduce_early(inside.read_inside(path), steps)


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


def early_ms(run):
    """Device self time a step under `hetu_moe_route_early`, all phases;
    None without."""
    r = for_run(run)
    return sum(r["early_ms_per_step"].values()) if r else None


def ahead_pct(run):
    """`reduce_early`'s "ahead_pct"; None without the scope."""
    r = for_run(run)
    return r["ahead_pct"] if r else None


def render(r):
    if not r:
        return "no hetu_moe_route_early scope in this trace"
    by = r["early_ms_per_step"]
    lead = r["lead_us_p50"]
    return "\n".join([
        f"{r['steps']} traced step(s); {r['device_self_ms_per_step']:.3f} "
        "ms device self time a step",
        "scope                      fwd  recompute       bwd     total",
        f"  {EARLY:<22}" + "".join(f"{by[p]:>10.3f}" for p in PHASES)
        + f"{sum(by.values()):>10.3f}",
        f"routing ended before the layer's forward flash call started in "
        f"{r['ahead']} of {r['layers']} expert layers of the traced forward "
        f"passes = {r['ahead_pct']:.1f} %; {r['ahead_time_pct']:.1f} % of "
        "the routing ops' forward time ran before it" if r["layers"] else
        "no forward flash call beside the routing ops",
        "" if lead is None else
        f"median lead (flash start - routing end): {lead:.1f} us"])


def main(argv):
    import argparse
    p = argparse.ArgumentParser(prog="python -m benchmark.reduce.smallthinker")
    p.add_argument("trace", help="a trace dir or one .xplane.pb")
    p.add_argument("--steps", type=int, default=None)
    a = p.parse_args(argv)
    path = a.trace if os.path.isfile(a.trace) else newest_xplane(a.trace)
    raw = inside.read_inside(path)
    steps = a.steps or inside.reduce_inside(raw)["steps"]
    print(render(reduce_early(raw, steps)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
