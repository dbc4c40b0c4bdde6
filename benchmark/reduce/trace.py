"""Profiler trace -> busy, idle, collective and kernel time.

`read_xplane` turns jax's `.xplane.pb` into plain lists (the form the test
fixture is written in); `reduce_trace` is pure Python over that form.

A TPU device plane (`/device:TPU:n`) has the lines `Steps`, `XLA Modules`,
`XLA Ops` and `Async XLA Ops`. Only `XLA Ops` is device busy time: a
`Steps` or `XLA Modules` event spans the ops it contains and counting it
would count them twice (hetuprof's `device_lanes` did: 22.7 % attributed,
PERF.md PR 21). `Async XLA Ops` holds the start-to-done spans of
asynchronous collectives and copies. Host and device events share one
clock. An op event is named by its whole HLO instruction
(`%fusion.3 = bf16[..] fusion(..), kind=kLoop, ..`): `parse_op` keeps the
instruction's name and its opcode, and a Mosaic (Pallas) kernel is a
custom call whose target is `tpu_custom_call`. Ops nest on the line (a
`while` spans its body), so times by family are self times.
"""
import glob
import os
import re

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast|send|recv)\b")
_INSTRUCTION = re.compile(r"^%?(\S+) = ")
_OPCODE = re.compile(r"(?:^|[ )])([a-z][a-z0-9-]*)\(")
MOSAIC = "mosaic"
NO_SPAN = "no_span"


def parse_op(text):
    """(instruction name, kind) of an `XLA Ops` event's name. kind is the
    HLO opcode, or MOSAIC for a `tpu_custom_call` custom call; a name that
    is no instruction text is kept whole with kind ""."""
    m = _INSTRUCTION.match(text)
    if not m:
        return text.lstrip("%"), ""
    if 'custom_call_target="tpu_custom_call"' in text:
        return m.group(1), MOSAIC
    op = _OPCODE.search(text, m.end())
    return m.group(1), op.group(1) if op else ""


def newest_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_xplane(path):
    """{"planes": [{"name", "lines": [{"name", "events":
    [[name, start_ns, duration_ns, kind], ...]}]}]}; on a device plane's
    op lines name and kind come from `parse_op`."""
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        is_device = bool(_DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            ops = is_device and line.name in (OPS_LINE, ASYNC_LINE)
            events = []
            for e in line.events:
                name, kind = parse_op(e.name) if ops else (e.name, "")
                events.append([name, float(e.start_ns),
                               float(e.duration_ns), kind])
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def family(name, kind=""):
    """`%fusion.123` -> `fusion`; `all-reduce-start.4` -> `all-reduce-start`;
    a Mosaic kernel's family is marked `mosaic:`."""
    base = name.lstrip("%")
    while True:
        m = re.match(r"^(.*?)[._]\d+$", base)
        if not m or not m.group(1):
            break
        base = m.group(1)
    return f"{MOSAIC}:{base}" if kind == MOSAIC else base


def is_collective(name, kind=""):
    return bool(_COLLECTIVE.match(kind) or _COLLECTIVE.match(name.lstrip("%")))


def union(intervals):
    """Merged, sorted [(start, end)] of possibly overlapping intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals):
    return sum(b - a for a, b in intervals)


def subtract(a, b):
    """The part of merged intervals `a` not covered by merged `b`."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def self_times(events):
    """[(event, self_ns)]: an event's duration less the events nested in
    it on the same line (a `while` spans its body's ops)."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    selfs = [e[2] for e in order]
    stack = []                       # indices of open events
    for i, e in enumerate(order):
        while stack and order[stack[-1]][1] + order[stack[-1]][2] <= e[1]:
            stack.pop()
        if stack:
            selfs[stack[-1]] -= e[2]
        stack.append(i)
    return [(e, max(0.0, s)) for e, s in zip(order, selfs)]


def _line(plane, name):
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def _host_spans(raw, span_names):
    names = set(span_names)
    spans = []
    for plane in raw["planes"]:
        if _DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            spans += [(e[1], e[1] + e[2], e[0]) for e in line["events"]
                      if e[0] in names]
    return sorted(spans)


def _label(t, spans):
    """The innermost benchmark span open on the host at time `t`."""
    best = None
    for a, b, name in spans:
        if a > t:
            break
        if b >= t and (best is None or a >= best[0]):
            best = (a, name)
    return best[1] if best else NO_SPAN


def reduce_trace(raw, span_names=()):
    """All times in seconds. The window is the host's first span start to
    its last span end where the trace has the benchmark's spans, else the
    first to the last device op."""
    chips = []
    for plane in raw["planes"]:
        m = _DEVICE_PLANE.match(plane["name"])
        if m:
            chips.append((int(m.group(1)), plane))
    chips.sort(key=lambda c: c[0])
    if not chips:
        raise ValueError("the trace has no /device:TPU:n plane: planes are "
                         f"{[p['name'] for p in raw['planes']]}")
    host = _host_spans(raw, span_names)
    all_ops = [e for _i, p in chips for e in _line(p, OPS_LINE)]
    if not all_ops:
        raise ValueError(f"no event on any {OPS_LINE!r} line: no operation "
                         "ran on the device in the traced window")
    if host:
        lo, hi = host[0][0], max(b for _a, b, _n in host)
    else:
        lo = min(e[1] for e in all_ops)
        hi = max(e[1] + e[2] for e in all_ops)
    window = hi - lo

    per_chip, fam_ns, gaps_by_chip = [], {}, []
    for idx, plane in chips:
        ops = [e for e in _line(plane, OPS_LINE)
               if e[1] + e[2] > lo and e[1] < hi]
        asyncs = [e for e in _line(plane, ASYNC_LINE)
                  if e[1] + e[2] > lo and e[1] < hi]
        iv = lambda evs: clip(union((e[1], e[1] + e[2]) for e in evs), lo, hi)
        busy = iv(ops)
        coll = iv([e for e in ops + asyncs if is_collective(e[0], e[3])])
        compute = iv([e for e in ops if not is_collective(e[0], e[3])])
        exposed = subtract(coll, compute)
        mosaic_ns = total_self = 0.0
        for e, s in self_times(ops):
            total_self += s
            if e[3] == MOSAIC:
                mosaic_ns += s
            fam = family(e[0], e[3])
            fam_ns[fam] = fam_ns.get(fam, 0.0) + s
        gaps = subtract([(lo, hi)], busy)
        gaps_by_chip.append(gaps)
        per_chip.append({
            "chip": idx, "busy_s": total(busy) / 1e9,
            "idle_pct": 100.0 * (1.0 - total(busy) / window),
            "collective_s": total(coll) / 1e9,
            "collective_exposed_s": total(exposed) / 1e9,
            "mosaic_s": mosaic_ns / 1e9, "ops_self_s": total_self / 1e9,
            "n_ops": len(ops)})

    # idle gaps of the idlest chip, by what the host was doing
    worst = max(range(len(per_chip)), key=lambda i: per_chip[i]["idle_pct"])
    by_label = {}
    for a, b in gaps_by_chip[worst]:
        label = _label((a + b) / 2.0, host)
        by_label[label] = by_label.get(label, 0.0) + (b - a) / 1e9
    n = len(per_chip)
    return {
        "window_s": window / 1e9,
        "busy_s": sum(c["busy_s"] for c in per_chip) / n,
        "chips": per_chip,
        "device_ops": sorted(([f, ns / 1e9 / n] for f, ns in fam_ns.items()),
                             key=lambda r: -r[1]),
        "idle_gaps": sorted(([k, v] for k, v in by_label.items()),
                            key=lambda r: -r[1]),
        "host_spans": len(host),
    }
