"""The program's own record of its start-up -> the seven `setup_*` metrics
(`python -m benchmark.reduce.startup` has no trace to read: the numbers come
from the running process, `hetu_tpu.telemetry.tracing.compile_log()` and
`import_records()`).

The program keeps one record a compiled program, fed by jax's `monitoring`
events: `trace`, `lower` and `backend` as `(start, end)` spans on
`time.perf_counter()`'s clock, the clock of harness/spans.py, and `cache`
(`"hit"`, `"miss"`, or None where the persistent cache was not asked). A
jitted function traced inside a jitted function reports its trace twice, and
a program that runs eagerly while another is traced compiles inside that
trace: every total here is the UNION of the spans of a thread, by kind, and
trace + lower is counted less what a compile or an import covers (the first
`import hetu_tpu.kernels` brings Pallas, and where a model defers it to its
first traced call it lies inside that program's trace).

A program that lacks the log (the parent of the PR that added it), and one
whose log hears nothing from jax, reads as "nothing": `value` returns None
for it and does not raise, so the traced line leaves the metric out."""
from .trace import clip, subtract, total, union

METRICS = ("import_s", "trace_lower_s", "compile_s", "cache_read_s",
           "cache_miss_programs", "programs", "warmup_steps_s")
# the benchmark's spans a step opens first (harness/spans.py; one of the two
# by adapter), and the read of the loss that ends `window.warm_up`
STEP_SPANS = ("feed", "run_call")
SYNC_SPAN = "sync"


def _by_thread(records, parts, keep=lambda r: True):
    """{thread: merged spans of `parts` over the records `keep` admits}."""
    out = {}
    for r in records:
        if keep(r):
            out.setdefault(r.get("thread"), []).extend(
                r[p] for p in parts if r.get(p) is not None)
    return {t: union(s) for t, s in out.items()}


def warm_up_window(spans, until):
    """`(start, end)` of the warm-up: the first step span's start to the end
    of the first read of the loss after it (`window.warm_up` runs its steps
    and reads once, before the traced window opens), or None where no such
    pair ends before `until`."""
    starts = [a for name in STEP_SPANS for a, _ in spans.get(name, ())]
    if not starts:
        return None
    start = min(starts)
    ends = sorted(b for a, b in spans.get(SYNC_SPAN, ())
                  if a >= start and b <= until)
    return (start, ends[0]) if ends else None


def reduce_startup(log, imports, spans, until):
    """The seven values, from `log` (the program's compile records),
    `imports` (its import records: `hetu.import`, `hetu.import.kernels`),
    `spans` (the benchmark's own: name -> [(start, end)]) and `until`, where
    `setup_s` ends: programs and imports that end later (the check's, the
    reference's) are out."""
    before = [r for r in log if r["end"] <= until]
    backend = _by_thread(before, ("backend",))
    trace_lower = _by_thread(before, ("trace", "lower"))
    importing = union([(r["start"], r["end"]) for r in imports
                       if r["end"] <= until])
    out = {
        "import_s": float(total(importing)),
        "trace_lower_s": sum(
            (total(subtract(s, union(backend.get(t, []) + importing)))
             for t, s in trace_lower.items()), 0.0),
        "compile_s": sum(map(total, _by_thread(
            before, ("backend",), lambda r: r["cache"] != "hit").values()), 0.0),
        "cache_read_s": sum(map(total, _by_thread(
            before, ("backend",), lambda r: r["cache"] == "hit").values()), 0.0),
        "cache_miss_programs": sum(r["cache"] == "miss" for r in before),
        "programs": len(before),
        "warmup_steps_s": 0.0,
    }
    window = warm_up_window(spans, until)
    if window is not None:
        compiling = union(
            [r[p] for r in before for p in ("trace", "lower", "backend")
             if r.get(p) is not None] + importing)
        out["warmup_steps_s"] = (window[1] - window[0]) - total(
            clip(compiling, *window))
    return out


def program_log():
    """`(compile_log(), import_records())` of this process's program, or None
    where the program keeps no such log or jax's events do not reach it."""
    from hetu_tpu.telemetry import tracing
    if not hasattr(tracing, "compile_log"):
        return None     # the parent of the PR that added the log
    if not tracing.compile_log_stats()["listening"]:
        return None
    return tracing.compile_log(), tracing.import_records()


def value(run, key):
    """`reduce_startup`'s `key` for a harness run object, cut where the
    measured window opens; None without the program's log."""
    read = program_log()
    if read is None:
        return None
    return reduce_startup(*read, run["spans"].records,
                          run["window"].sync_times[0])[key]
