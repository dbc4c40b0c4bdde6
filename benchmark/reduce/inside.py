"""The program's own names in a profiler trace -> time by kernel, by step
phase and by host span (`python -m benchmark.reduce.inside <trace dir>`
prints the whole table).

The program writes one vocabulary (hetu_tpu/telemetry/tracing.py,
docs/OBSERVABILITY.md) into whatever capture is open; this file reads it
back. A program that lacks a name (the parent of the PR that added it)
reads as "nothing": every function here returns None or an empty table for
it and does not raise.

Where a device op's `op_name` comes from (settled on a real v5e trace, PR
23; tests/fixtures/inside_two_chips.json is cut from such a trace): not
from the event's name, which is the HLO instruction text WITHOUT its
`metadata={...}`, and not from a stat on the event, but from the `tf_op`
stat of the event's XEventMetadata (`jit(step)/transpose(jvp(hetu_fwd))/
while/body/closed_call/checkpoint/rematted_computation/flash_fwd/
pallas_call:`). `jax.profiler.ProfileData` shows an event's own stats
only, so `op_names` reads that one stat straight from the protobuf wire
format; times and host events still come through ProfileData, as in
reduce/trace.py.

The phase of a device op, from its `op_name` path:

    a collective (by opcode)                      -> collective
    under `hetu_opt`                              -> opt
    under a `transpose(`, in `rematted_computation`  -> recompute
    under a `transpose(` otherwise                -> bwd
    any other path                                -> fwd
    no path at all (copies, converts and waits the compiler put in): the
    phase of the op that consumes its result and runs longest, else
    `unattributed`
"""
import functools
import os
import re
import statistics
import sys
import traceback

from . import kernel_flops
from .trace import (MOSAIC, OPS_LINE, family, is_collective, newest_xplane,
                    parse_op, self_times, subtract, total, union)

# the program's vocabulary as this reader expects it (a copy: the benchmark
# also runs programs that lack it)
STEP = "hetu_step"
SCOPE_FWD, SCOPE_OPT = "hetu_fwd", "hetu_opt"
SPANS = ("hetu.boundary", "hetu.feed", "hetu.dl_wait", "hetu.ps_pull",
         "hetu.build", "hetu.dispatch", "hetu.prefetch", "hetu.ps_push",
         "hetu.poststep")
# the benchmark's own spans around a call into the program (harness/spans.py
# writes them into the same trace): the view from outside
OUTSIDE = ("run_call", "step_call")
PHASES = ("fwd", "recompute", "bwd", "opt", "collective")
UNATTRIBUTED = "unattributed"
MODULES_LINE = "XLA Modules"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPERAND = re.compile(r"%([\w.\-]+)")
_RESULT_SHAPE = re.compile(r" = \(?\w+\[(\d+),(\d+),(\d+)\]")


# -- the protobuf wire format, as far as XEventMetadata's stats ---------------
# xplane.proto: XSpace.planes=1; XPlane.name=2 .lines=3 .event_metadata=4
# .stat_metadata=5 (maps: key=1, value=2); XEventMetadata.name=2 .stats=5;
# XStatMetadata.id=1 .name=2; XStat.metadata_id=1 .str_value=5 .ref_value=7

def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} in an xplane message")
        yield key >> 3, value


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def _map_value(entry):
    for num, value in _fields(entry):
        if num == 2:
            return value
    return b""


def op_names(path):
    """{device plane name: {event name: op_name}} from the `tf_op` stat of
    each XEventMetadata; a plane without the stat gives an empty dict."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        name, events, stats = "", [], {}
        for n, value in _fields(plane):
            if n == 2:
                name = _text(value)
            elif n == 4:
                events.append(_map_value(value))
            elif n == 5:
                sid, sname = 0, ""
                for m, v in _fields(_map_value(value)):
                    if m == 1:
                        sid = v
                    elif m == 2:
                        sname = _text(v)
                stats[sid] = sname
        if not _DEVICE_PLANE.match(name):
            continue
        by_event = out.setdefault(name, {})
        for meta in events:
            event_name = op = ""
            for n, value in _fields(meta):
                if n == 2:
                    event_name = _text(value)
                elif n == 5:
                    sid, text, ref = 0, "", None
                    for m, v in _fields(value):
                        if m == 1:
                            sid = v
                        elif m == 5:
                            text = _text(v)
                        elif m == 7:
                            ref = v
                    if stats.get(sid) == "tf_op":
                        op = text or stats.get(ref, "")
            if op:
                by_event[event_name] = op
    return out


# -- reading --------------------------------------------------------------------

def read_inside(path):
    """The trace as plain lists (the form the test fixture is written in):

    {"chips": [{"chip": n, "modules": [name, ...],
                "ops": [[instruction text, start_ns, dur_ns, op_name], ...]}],
     "host": [[span name, start_ns, dur_ns, thread line, {arg: value}], ...]}

    `ops` is the `XLA Ops` line, `modules` the names on `XLA Modules` (one a
    program run), `host` every event whose name starts with `hetu` and the
    benchmark's own `run_call` / `step_call` spans."""
    from jax.profiler import ProfileData
    names = op_names(path)
    chips, host = [], []
    for plane in ProfileData.from_file(path).planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            by_event = names.get(plane.name, {})
            chip = {"chip": int(m.group(1)), "modules": [], "ops": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    chip["ops"] = [[e.name, float(e.start_ns),
                                    float(e.duration_ns),
                                    by_event.get(e.name, "")]
                                   for e in line.events]
                elif line.name == MODULES_LINE:
                    chip["modules"] = [e.name for e in line.events]
            chips.append(chip)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("hetu") or e.name in OUTSIDE:
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns), line.name,
                                     {k: v for k, v in e.stats
                                      if not k.startswith("_")}])
    chips.sort(key=lambda c: c["chip"])
    return {"chips": chips, "host": host}


# -- reducing -------------------------------------------------------------------

def phase_of(name, kind, op_name):
    """The phase of one device op (module docstring), None for an op with
    no `op_name` path."""
    if is_collective(name, kind):
        return "collective"
    if not op_name:
        return None
    if SCOPE_OPT in op_name:
        return "opt"
    if "transpose(" in op_name:
        return "recompute" if "rematted_computation" in op_name else "bwd"
    return "fwd"


def _reduce_chip(ops):
    """One chip's `XLA Ops` line -> self time by instruction, each with its
    phase; ns."""
    insts = {}      # instruction name -> record
    events = []
    for text, start, dur, op_name in ops:
        name, kind = parse_op(text)
        if name not in insts:
            insts[name] = {"name": name, "kind": kind, "text": text,
                           "op_name": op_name, "self_ns": 0.0, "calls": 0,
                           "phase": phase_of(name, kind, op_name)}
        events.append([name, start, dur, kind])
    for e, s in self_times(events):
        insts[e[0]]["self_ns"] += s
        insts[e[0]]["calls"] += 1
    # an op without a path takes the phase of its longest-running consumer
    for _round in range(3):
        orphans = {n for n, r in insts.items() if r["phase"] is None}
        if not orphans:
            break
        best = {}
        for r in insts.values():
            if r["phase"] is None:
                continue
            operands = r["text"].split(" = ", 1)[-1]
            for used in set(_OPERAND.findall(operands)) & orphans:
                if used not in best or r["self_ns"] > best[used]["self_ns"]:
                    best[used] = r
        if not best:
            break
        for n, consumer in best.items():
            insts[n]["phase"] = consumer["phase"]
    busy = union((e[1], e[1] + e[2]) for e in events)
    return insts, busy


def _flash_flops(records):
    """Operations one chip's flash calls required (padded keys counted, a
    recomputed forward counted as run), each call's shape (B*H, T, d) read
    from the result in its own instruction text. The backward is one
    required computation however many kernels share it (dq; dk+dv): the
    kernel family with the most calls counts."""
    fwd, bwd = 0.0, {}
    for r in records:
        m = _RESULT_SHAPE.search(r["text"])
        if not m:
            continue
        shape = tuple(int(g) for g in m.groups())
        kernel = family(r["name"])
        if "flash_fwd" in kernel:
            fwd += r["calls"] * kernel_flops.flash_fwd_flops(*shape)
        elif "flash_bwd" in kernel:
            bwd[kernel] = bwd.get(kernel, 0.0) \
                + r["calls"] * kernel_flops.flash_bwd_flops(*shape)
    return fwd + max(bwd.values(), default=0.0)


def _host_steps(host):
    """[(step span, {child name: summed ns}, [child names in time order])]
    of the thread line that holds the most `hetu_step` spans."""
    lines = {}
    for e in host:
        lines.setdefault(e[3], []).append(e)
    main = max(lines.values(), default=[],
               key=lambda evs: sum(e[0] == STEP for e in evs))
    steps = sorted((e for e in main if e[0] == STEP), key=lambda e: e[1])
    children = sorted((e for e in main if e[0] in SPANS), key=lambda e: e[1])
    out, j = [], 0
    for s in steps:
        lo, hi = s[1], s[1] + s[2]
        while j < len(children) and children[j][1] < lo:
            j += 1
        sums, order, k = {}, [], j
        while k < len(children) and children[k][1] + children[k][2] <= hi:
            c = children[k]
            sums[c[0]] = sums.get(c[0], 0.0) + c[2]
            order.append(c[0])
            k += 1
        out.append((s, sums, order))
    return out


def reduce_inside(raw, steps=None):
    """Times in ms unless named otherwise. `steps` is the number of traced
    steps (the harness knows it); without it, the runs of the most frequent
    program on `XLA Modules`."""
    chips = raw["chips"]
    host_steps = _host_steps(raw["host"])
    if steps is None:
        counts = {}
        for c in chips:
            for m in c["modules"]:
                counts[(c["chip"], m)] = counts.get((c["chip"], m), 0) + 1
        steps = max(counts.values(), default=len(host_steps))
    steps = max(int(steps), 1)
    n = max(len(chips), 1)

    phase_ns = dict.fromkeys(PHASES + (UNATTRIBUTED,), 0.0)
    kernels, attributed, busy_by_chip = {}, [], []
    flash_ns = flash_flops = self_ns = 0.0
    scoped = False
    for chip in chips:
        insts, busy = _reduce_chip(chip["ops"])
        busy_by_chip.append(busy)
        chip_self = chip_unattributed = 0.0
        for r in insts.values():
            phase = r["phase"] or UNATTRIBUTED
            phase_ns[phase] += r["self_ns"]
            chip_self += r["self_ns"]
            if r["phase"] is None:
                chip_unattributed += r["self_ns"]
            scoped = scoped or SCOPE_OPT in r["op_name"] \
                or SCOPE_FWD in r["op_name"]
            if r["kind"] != MOSAIC:
                continue
            kernel = family(r["name"])
            row = kernels.setdefault((kernel, phase), [0, 0.0])
            row[0] += r["calls"]
            row[1] += r["self_ns"]
            if "flash_" in kernel:
                flash_ns += r["self_ns"]
        flash_flops += _flash_flops(
            r for r in insts.values()
            if r["kind"] == MOSAIC and "flash_" in r["name"])
        self_ns += chip_self
        attributed.append(100.0 * (1.0 - chip_unattributed / chip_self)
                          if chip_self else 0.0)

    outside = [e[2] for e in raw["host"] if e[0] in OUTSIDE]
    out = {
        "chips": len(chips), "steps": steps, "scoped": scoped,
        "device_self_ms_per_step": self_ns / 1e6 / n / steps,
        "phase_ms_per_step": {p: ns / 1e6 / n / steps
                              for p, ns in phase_ns.items()},
        "attributed_pct_min": min(attributed, default=0.0),
        "kernels": sorted(
            ({"kernel": k, "phase": p, "calls_per_step": c / n / steps,
              "ms_per_step": ns / 1e6 / n / steps,
              "ms_per_call": ns / 1e6 / c if c else 0.0,
              "time_pct": 100.0 * ns / self_ns if self_ns else 0.0}
             for (k, p), (c, ns) in kernels.items()),
            key=lambda r: -r["ms_per_step"]),
        "flash": {"time_pct": 100.0 * flash_ns / self_ns if self_ns else 0.0,
                  "seconds": flash_ns / 1e9, "flops": flash_flops}
        if flash_ns else None,
        "host": None, "starved_pct": None,
        "outside_ms_p50": statistics.median(outside) / 1e6
        if outside else None,
    }
    if not host_steps:
        return out

    per_step = {name: [] for name in SPANS}
    coverage, durations, in_order = [], [], True
    for s, sums, order in host_steps:
        durations.append(s[2])
        coverage.append(100.0 * sum(sums.values()) / s[2] if s[2] else 0.0)
        in_order = in_order and order == [x for x in SPANS if x in order]
        for name in SPANS:
            per_step[name].append(sums.get(name, 0.0))

    def p50(*names):
        return statistics.median(
            sum(vals) for vals in zip(*(per_step[x] for x in names))) / 1e6

    out["host"] = {
        "steps": len(host_steps),
        "step_nums": [s[4].get("step_num") for s, _sums, _o in host_steps],
        "compiled": sum(1 for e in raw["host"]
                        if e[0] == "hetu.build" and e[4].get("compiled")),
        "step_ms_p50": statistics.median(durations) / 1e6,
        "coverage_pct_p50": statistics.median(coverage),
        "span_ms_p50": {name: p50(name) for name in SPANS},
        "input_ms_p50": p50("hetu.feed", "hetu.dl_wait"),
        "dispatch_ms_p50": p50("hetu.dispatch"),
        "poststep_ms_p50": p50("hetu.prefetch", "hetu.poststep"),
        "ps_blocked_ms_per_step": (
            sum(per_step["hetu.ps_pull"]) + sum(per_step["hetu.ps_push"]))
        / 1e6 / len(host_steps),
        "coverage_pct_min": min(coverage),
        "children_in_order": in_order,
    }
    if chips:
        # the window: the first hetu_step's start to the last one's end, or
        # to the last device op's where that is later
        spans = union((s[1], s[1] + s[2]) for s, _sums, _o in host_steps)
        lo = spans[0][0]
        hi = max([spans[-1][1]] + [b[-1][1] for b in busy_by_chip if b])
        gaps = max((subtract([(lo, hi)], busy) for busy in busy_by_chip),
                   key=total)                       # the idlest chip's
        starved = total(gaps) - total(subtract(gaps, spans))
        out["starved_pct"] = 100.0 * starved / (hi - lo)
        out["window_ms"] = (hi - lo) / 1e6
    return out


# -- what a reader (layer_metrics/*.py) asks for --------------------------------

@functools.lru_cache(maxsize=4)
def _reduced(path, steps):
    return reduce_inside(read_inside(path), steps)


def for_run(run):
    """The reduced inside of a traced run's own trace, or None: an
    end-to-end run, no trace on disk, or a trace this file cannot read (the
    reason goes to stderr; a reader then leaves its metric out)."""
    t = run.get("trace")
    if not t:
        return None
    cell = run["cell"]
    try:
        path = newest_xplane(os.path.join(cell.bench_dir, ".cache", "trace",
                                          cell.name))
        return _reduced(path, t.get("steps") or None)
    except Exception:       # noqa: BLE001 - a reader returns nothing instead
        traceback.print_exc()
        return None


def phase_ms(run, phase):
    """`<phase>_ms_per_step`: device self time of one phase over the traced
    steps, mean over chips; None where the program wrote no phase scope or
    the phase did not occur."""
    r = for_run(run)
    if not r or not r["scoped"]:
        return None
    return r["phase_ms_per_step"][phase] or None


def host_value(run, key):
    r = for_run(run)
    return r["host"][key] if r and r["host"] else None


# -- the table a human wants ----------------------------------------------------

def render(r, tflops=None):
    lines = [f"{r['chips']} chip(s), {r['steps']} traced step(s); device "
             f"self time {r['device_self_ms_per_step']:.3f} ms a step a chip"]
    lines.append("phase            ms/step   share")
    whole = r["device_self_ms_per_step"] or 1.0
    for p in PHASES + (UNATTRIBUTED,):
        ms = r["phase_ms_per_step"][p]
        lines.append(f"  {p:<14} {ms:>9.3f}  {100.0 * ms / whole:>5.1f} %")
    lines.append(f"  attributed: {r['attributed_pct_min']:.2f} % of device "
                 "self time on the least attributed chip"
                 + ("" if r["scoped"] else
                    " (no hetu_fwd / hetu_opt scope in this program)"))
    lines.append("kernel                phase      calls/step  ms/call  "
                 "ms/step  share")
    for k in r["kernels"]:
        lines.append(f"  {k['kernel']:<19} {k['phase']:<10} "
                     f"{k['calls_per_step']:>10.1f} {k['ms_per_call']:>8.3f} "
                     f"{k['ms_per_step']:>8.3f} {k['time_pct']:>5.1f} %")
    f = r["flash"]
    if f:
        line = (f"flash attention: {f['time_pct']:.1f} % of device self "
                f"time, {f['flops'] / 1e12:.2f} TFLOP required in "
                f"{f['seconds']:.3f} s = "
                f"{f['flops'] / f['seconds'] / 1e12:.1f} TFLOP/s")
        if tflops:
            share = 100.0 * f["flops"] / f["seconds"] / (tflops * 1e12)
            line += f", {share:.1f} % of {tflops:g} TFLOP/s"
        lines.append(line)
    if r["outside_ms_p50"]:
        lines.append("the benchmark's own span around the call, in this "
                     f"capture: median {r['outside_ms_p50']:.4f} ms")
    h = r["host"]
    if h:
        lines.append(f"host: {h['steps']} {STEP} span(s), median "
                     f"{h['step_ms_p50']:.4f} ms; children cover {h['coverage_pct_p50']:.1f} % of "
                     f"the median step, >= {h['coverage_pct_min']:.1f} % of "
                     f"each, in table order: {h['children_in_order']}; "
                     f"{h['compiled']} compiled")
        for name in SPANS:
            lines.append(f"  {name:<14} p50 {h['span_ms_p50'][name]:.4f} ms")
        lines.append(f"  input {h['input_ms_p50']:.4f}  dispatch "
                     f"{h['dispatch_ms_p50']:.4f}  poststep "
                     f"{h['poststep_ms_p50']:.4f}  ps blocked "
                     f"{h['ps_blocked_ms_per_step']:.4f} ms a step")
    if r["starved_pct"] is not None:
        lines.append(f"starved: the idlest chip runs nothing inside {STEP} "
                     f"for {r['starved_pct']:.3f} % of a "
                     f"{r['window_ms']:.1f} ms window")
    return "\n".join(lines)


def main(argv):
    import argparse
    from . import peaks
    p = argparse.ArgumentParser(prog="python -m benchmark.reduce.inside")
    p.add_argument("trace", help="a trace dir (the newest .xplane.pb under "
                                 "it) or one .xplane.pb")
    p.add_argument("--steps", type=int, default=None)
    p.add_argument("--device-kind", default="TPU v5 lite")
    a = p.parse_args(argv)
    path = a.trace if os.path.isfile(a.trace) else newest_xplane(a.trace)
    print(render(reduce_inside(read_inside(path), a.steps),
                 peaks.peaks(a.device_kind)["tflops"]))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
