"""One run of one cell: set-up, the window, the checks, the last line."""
import argparse
import contextlib
import json
import os
import shutil
import signal
import sys
import time

from . import device, manifest, window
from .spans import Spans


def _parse(argv):
    p = argparse.ArgumentParser(prog="benchmark/run.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), required=True)
    return p.parse_args(argv)


def _on_signal(signum, _frame):
    # a cut run still unwinds through close(): no PS server is left behind
    raise SystemExit(128 + signum)


def _traced_window(job, cell, steps, spans):
    """`steps` steps under the jax profiler; returns the reduced trace."""
    import jax
    from ..reduce import trace as trace_reduce
    out = os.path.join(cell.bench_dir, ".cache", "trace", cell.name)
    shutil.rmtree(out, ignore_errors=True)
    jax.profiler.start_trace(out)
    try:
        win = window.run(job, 0.0, cell.traffic["sync_every"],
                         max_steps=steps)
    finally:
        jax.profiler.stop_trace()
    raw = trace_reduce.read_xplane(trace_reduce.newest_xplane(out))
    reduced = trace_reduce.reduce_trace(raw, sorted(spans.records))
    reduced["steps"] = win.steps
    return reduced


def run_cell(cell, seed, seconds, trace, t0):
    """Returns the result object of the last line."""
    phases, mark = {}, t0

    def phase(name):
        nonlocal mark
        now = time.perf_counter()
        phases[name + "_s"] = now - mark
        mark = now

    device.restrict_visible_chips(cell.chips)
    device.use_compile_cache(cell.bench_dir)
    devices = device.require(cell.chips)
    counter = device.CompileCounter()
    phase("import_and_reach_chip")
    spans = Spans(enabled=trace)
    batches = manifest.generator(cell).generate(cell.traffic, cell.config,
                                                seed)
    phase("generate_traffic")
    with contextlib.ExitStack() as stack:
        job = manifest.adapter(cell).build(
            cell.config, cell.traffic, seed, devices, batches, spans)
        stack.callback(job.close)
        phase("build_job")
        # the Executor compiles on its first and its second call: >= 3
        window.warm_up(job, cell.traffic.get("warmup_steps", 3))
        phase("compile_and_warm_up")
        phases["cache_misses"] = counter.misses
        reduced = None
        if trace:
            reduced = _traced_window(
                job, cell, cell.traffic["trace_steps"], spans)
        setup_s = time.perf_counter() - t0
        win = window.run(job, seconds, cell.traffic["sync_every"], counter)
        mark = time.perf_counter()
        check = job.check(manifest.reference(cell))
        counters = job.counters()
        phase("check")
        peak = device.memory_peak_bytes(devices)
        dev = device.describe(devices)
        dev["memory_stats"] = devices[0].memory_stats()

    items_per_s = win.steps_per_s * job.items_per_step
    dev["memory_peak_bytes"] = peak
    run = {"cell": cell, "window": win, "items_per_s": items_per_s,
           "setup_s": setup_s, "trace": reduced, "spans": spans,
           "counters": counters, "device": dev, "chips": cell.chips,
           "compiles_in_window": win.compiles, "check": check}
    metrics = {}
    if trace:
        for m in cell.per_layer:
            value = manifest.reader(cell, m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
    else:
        values = {"setup_s": setup_s,
                  cell.traffic["throughput_metric"]: items_per_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    result = {"correct": bool(check["ok"]) and win.failed == 0,
              "attempted": win.attempted, "failed": win.failed,
              "metrics": metrics, "device": dev,
              "workload": cell.name, "seed": seed, "phases": phases,
              "window": {"steps": win.steps, "seconds": win.seconds,
                         "syncs": len(win.sync_times) - 1,
                         "compiles": win.compiles,
                         "first_loss": win.losses[0] if win.losses else None,
                         "last_loss": win.losses[-1] if win.losses else None},
              "check": check}
    if trace:
        result["breakdown"] = {"device_ops": reduced["device_ops"][:10],
                               "idle_gaps": reduced["idle_gaps"][:10]}
    return result


def main(argv, root, t0):
    args = _parse(argv)
    for sig in (signal.SIGTERM, signal.SIGINT, signal.SIGHUP):
        signal.signal(sig, _on_signal)
    try:
        cell = manifest.resolve(root, args.workload)
        # the program's banners go to stderr: stdout ends in the result line
        with contextlib.redirect_stdout(sys.stderr):
            result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                              t0)
    except (manifest.ManifestError, device.DeviceError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0
