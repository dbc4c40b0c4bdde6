"""Start-up measured inside the program (PR 51): the compile log of
`telemetry/tracing.py` (one record a compiled program, from jax's own
`monitoring` events), `hetu.import`, and the Executor's `compile_ms`."""
import contextlib
import functools
import importlib.util
import json
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu.telemetry import tracing as tr

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@contextlib.contextmanager
def _cache_at(path):
    """jax's persistent cache at `path` for the block, every program kept."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    old = [getattr(jax.config, n) for n in names]
    try:
        for n, v in zip(names, (str(path), 0, 0)):
            jax.config.update(n, v)
        cc.reset_cache()
        yield
    finally:
        for n, v in zip(names, old):
            jax.config.update(n, v)
        cc.reset_cache()


def _named(name):
    def f(x):
        return jnp.tanh(x * 3.0 + 1.0).sum()
    f.__name__ = f.__qualname__ = name
    return jax.jit(f)


def _by_name(records, name):
    return [r for r in records if r["fun_name"] == f"jit({name})"]


def test_a_jitted_function_leaves_one_record_on_perf_counters_clock():
    f = _named("startup_one_record")
    t0 = time.perf_counter()
    f(jnp.ones((4, 4)))
    t1 = time.perf_counter()
    (rec,) = _by_name(tr.compile_log(), "startup_one_record")
    assert rec["thread"] == threading.get_ident()
    for part in tr.COMPILE_PARTS:
        start, end = rec[part]
        assert rec[part + "_s"] >= 0 and end >= start
        assert rec[part + "_s"] == pytest.approx(end - start, abs=1e-6)
        # jax stamps time.time(); the log converts with the module's anchors
        assert t0 - 0.05 <= start and end <= t1 + 0.05
    assert rec["trace"][1] <= rec["lower"][0] + 1e-3
    assert rec["lower"][1] <= rec["backend"][0] + 1e-3
    assert rec["end"] == rec["backend"][1]
    # a second call compiles nothing
    f(jnp.ones((4, 4)))
    assert len(_by_name(tr.compile_log(), "startup_one_record")) == 1


def test_one_function_reads_miss_then_hit(tmp_path):
    # a fresh `jax.jit` of the same function is the same program to the
    # persistent cache and a new one to jax's in-memory ones (no
    # `jax.clear_caches()`: the other tests of this process keep theirs)
    with _cache_at(tmp_path):
        _named("startup_hit_and_miss")(jnp.ones((8, 8)))
        _named("startup_hit_and_miss")(jnp.ones((8, 8)))
    miss, hit = _by_name(tr.compile_log(), "startup_hit_and_miss")
    assert miss["cache"] == "miss" and miss["cache_read_s"] == 0.0
    assert hit["cache"] == "hit" and hit["cache_read_s"] > 0
    assert hit["backend_s"] < miss["backend_s"]
    # the read is inside the backend span that closed the record
    assert hit["cache_read_s"] <= hit["backend_s"] + 1e-3
    # both paid the trace and the lowering: a warm start does too
    assert hit["trace_s"] > 0 and hit["lower_s"] > 0


def test_a_program_that_does_not_ask_the_cache_reads_none():
    from jax.experimental.compilation_cache import compilation_cache as cc
    old = jax.config.jax_enable_compilation_cache
    try:
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        _named("startup_cache_not_asked")(jnp.ones(3))
    finally:
        jax.config.update("jax_enable_compilation_cache", old)
        cc.reset_cache()
    (rec,) = _by_name(tr.compile_log(), "startup_cache_not_asked")
    assert rec["cache"] is None and rec["backend_s"] > 0


def test_a_jitted_function_in_a_jitted_function_is_counted_once():
    @jax.jit
    def startup_inner(x):
        return jnp.sin(x) * 2.0

    @jax.jit
    def startup_outer(x):
        return startup_inner(x) + startup_inner(x * 3.0)

    from jax import monitoring
    traced = []      # every trace event jax sends, as the log hears them

    def listener(event, start, end, fun_name="", **_kw):
        if event == tr.EV_TRACE:
            traced.append((fun_name, end - start))

    monitoring.register_event_time_span_listener(listener)
    try:
        startup_outer(jnp.ones(5))
    finally:
        monitoring.unregister_event_time_span_listener(listener)
    records = tr.compile_log()
    (rec,) = _by_name(records, "startup_outer")
    # the inner function is part of the outer program, not one of its own
    assert not _by_name(records, "startup_inner")
    # jax reported sin, multiply, inner, ... and last the outermost, which
    # contains them all: the union is its span, the plain sum counts twice
    assert len(traced) >= 5 and traced[-1][0] == "startup_outer"
    union = tr.span_union([rec["trace"]])
    assert union == pytest.approx(rec["trace_s"])
    assert union == pytest.approx(traced[-1][1], abs=1e-6)
    assert union < sum(dur for _, dur in traced)


def test_since_and_until_cut_by_end():
    _named("startup_cut_a")(jnp.ones(2))
    mid = time.perf_counter()
    _named("startup_cut_b")(jnp.ones(2))
    (a,) = _by_name(tr.compile_log(), "startup_cut_a")
    (b,) = _by_name(tr.compile_log(), "startup_cut_b")
    assert a["end"] < mid < b["end"] and b["seq"] > a["seq"]
    assert _by_name(tr.compile_log(until=mid), "startup_cut_a")
    assert not _by_name(tr.compile_log(until=mid), "startup_cut_b")
    assert _by_name(tr.compile_log(since=mid), "startup_cut_b")
    assert not _by_name(tr.compile_log(since=mid), "startup_cut_a")
    assert tr.compile_log(since=a["end"], until=a["end"])[0]["seq"] == a["seq"]
    assert tr.compile_log(since=b["end"] + 1.0) == []


def _program(log, name, t, cache=None):
    """Feed `log` the events of one program as jax sends them; returns the
    next free time."""
    unix = tr._T0_UNIX - tr._T0_PERF
    log.on_span(tr.EV_TRACE, unix + t, unix + t + 1, fun_name=name)
    log.on_span(tr.EV_LOWER, unix + t + 1, unix + t + 2,
                fun_name=f"jit({name})")
    if cache is not None:
        log.on_event(tr.EV_CACHE_ASKED)
        log.on_event(tr.EV_CACHE_HIT if cache == "hit" else tr.EV_CACHE_MISS)
    log.on_span(tr.EV_BACKEND, unix + t + 2, unix + t + 3,
                fun_name=f"jit({name})")
    return t + 3


def test_the_log_drops_past_its_bound_and_counts_it():
    log = tr.CompileLog()
    bound = tr.COMPILE_LOG_BOUND
    assert bound == 1024 and tr._LOG._records.maxlen == bound
    t = 0.0
    for i in range(bound + 2):
        t = _program(log, f"p{i}", t)
    kept = log.records()
    assert len(kept) == bound
    assert [r["fun_name"] for r in kept[:2] + kept[-1:]] == [
        "jit(p2)", "jit(p3)", f"jit(p{bound + 1})"]
    assert [r["seq"] for r in kept] == list(range(3, bound + 3))
    assert log.dropped == 2 and log.seq == bound + 2
    stats = log.stats()
    assert stats["programs"] == bound and stats["dropped"] == 2
    assert stats["calls"] == 3 * (bound + 2) and stats["listener_s"] > 0


def test_two_threads_keep_their_hits_with_their_own_programs():
    log = tr.CompileLog()
    unix = tr._T0_UNIX - tr._T0_PERF
    turn = threading.Semaphore(0), threading.Semaphore(0)

    def worker(me, name, event, base):
        mine, other = turn[me], turn[1 - me]
        steps = (
            lambda: log.on_span(tr.EV_TRACE, unix + base, unix + base + 1,
                                fun_name=name),
            lambda: log.on_span(tr.EV_LOWER, unix + base + 1, unix + base + 2,
                                fun_name=f"jit({name})"),
            lambda: (log.on_event(tr.EV_CACHE_ASKED), log.on_event(event)),
            lambda: log.on_duration(tr.EV_CACHE_READ, 0.25 + me),
            lambda: log.on_span(tr.EV_BACKEND, unix + base + 2,
                                unix + base + 3, fun_name=f"jit({name})"))
        for step in steps:      # strictly alternating with the other thread
            mine.acquire()
            step()
            other.release()

    threads = [
        threading.Thread(target=worker,
                         args=(0, "on_a", tr.EV_CACHE_HIT, 10.0)),
        threading.Thread(target=worker,
                         args=(1, "on_b", tr.EV_CACHE_MISS, 10.5))]
    for t in threads:
        t.start()
    turn[0].release()
    for t in threads:
        t.join(10)
    turn[0].acquire(timeout=1)      # the last hand-over, left by thread b
    a, b = sorted(log.records(), key=lambda r: r["fun_name"])
    assert (a["fun_name"], a["cache"], a["cache_read_s"]) == (
        "jit(on_a)", "hit", 0.25)
    assert (b["fun_name"], b["cache"], b["cache_read_s"]) == (
        "jit(on_b)", "miss", 1.25)
    assert a["thread"] == threads[0].ident != b["thread"]
    assert a["trace"] == pytest.approx((10.0, 11.0))
    assert b["trace"] == pytest.approx((10.5, 11.5))


def test_many_threads_lose_no_program():
    """Eight threads close programs at once: no record, sequence number or
    drop is lost, and each thread's programs keep their order."""
    log = tr.CompileLog()
    n_threads, n_programs, bound = 8, 200, tr.COMPILE_LOG_BOUND

    def worker(i):
        t = 1000.0 * i
        for j in range(n_programs):
            t = _program(log, f"t{i}_{j}", t, "hit" if j % 2 else "miss")

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not any(t.is_alive() for t in threads)
    total = n_threads * n_programs
    kept = log.records()
    assert log.seq == total and len(kept) == bound
    assert log.dropped == total - bound
    assert [r["seq"] for r in kept] == list(
        range(total - bound + 1, total + 1))
    last = {}
    for r in kept:
        i, j = map(int, r["fun_name"][5:-1].split("_"))
        assert last.get(i, -1) < j
        last[i] = j
    for r in kept:
        i, j = map(int, r["fun_name"][5:-1].split("_"))
        assert r["cache"] == ("hit" if j % 2 else "miss")
        assert r["trace"] == pytest.approx((1000.0 * i + 3 * j,
                                            1000.0 * i + 3 * j + 1))
    assert log.stats()["calls"] == total * 5


def test_a_thread_keeps_only_its_outermost_trace_spans():
    log = tr.CompileLog()
    unix = tr._T0_UNIX - tr._T0_PERF

    def trace(name, start, end):
        log.on_span(tr.EV_TRACE, unix + start, unix + end, fun_name=name)

    trace("sin", 1.0, 1.1)          # innermost first ...
    trace("multiply", 1.2, 1.3)
    trace("inner", 0.9, 1.4)        # ... swallowed by what contains them
    trace("add", 1.5, 1.6)
    assert [p[2] for p in log._state().pending] == ["inner", "add"]
    trace("step", 0.5, 2.0)
    assert [p[2] for p in log._state().pending] == ["step"]
    # a trace no program follows (jax.eval_shape) stays pending: the next
    # program takes the span of its own name and leaves it
    trace("shape_only", 3.0, 3.5)
    trace("step2", 4.0, 4.5)
    # a lowering rule that traces a helper reports it before the lowering's
    # own span arrives: the lowering swallows it like a trace would
    trace("rule_helper", 4.6, 4.7)
    log.on_span(tr.EV_LOWER, unix + 4.5, unix + 5.0, fun_name="jit(step2)")
    log.on_span(tr.EV_BACKEND, unix + 5.0, unix + 6.0, fun_name="jit(step2)")
    (rec,) = log.records()
    assert rec["trace"] == pytest.approx((4.0, 4.5))
    assert [p[2] for p in log._state().pending] == ["step", "shape_only"]
    stats = log.stats()
    assert stats["pending_traces"] == 2
    assert stats["pending_trace_s"] == pytest.approx(2.0)


def test_a_lowering_that_is_never_compiled_keeps_its_record():
    lowered = _named("startup_lowered_only").lower(jnp.ones(3))
    _named("startup_after_lowered")(jnp.ones(3))     # closes the open one
    (rec,) = _by_name(tr.compile_log(), "startup_lowered_only")
    assert rec["backend"] is None and rec["backend_s"] == 0.0
    assert rec["lower_s"] > 0 and rec["end"] == rec["lower"][1]
    assert rec["cache"] is None
    # compiled later, from a lowering the log no longer holds open
    lowered.compile()
    late = _by_name(tr.compile_log(), "startup_lowered_only")[-1]
    assert late["lower"] is None and late["backend_s"] > 0


def test_span_union_and_clip():
    spans = [(0.0, 1.0), (0.5, 1.5), (0.6, 0.7), (3.0, 4.0)]
    assert tr.span_union(spans) == pytest.approx(2.5)
    assert tr.span_union(spans) < sum(e - s for s, e in spans)
    assert tr.span_union([]) == 0.0
    assert tr.clip_spans(spans, 0.75, 3.5) == [
        (0.75, 1.0), (0.75, 1.5), (3.0, 3.5)]
    assert tr.span_union(tr.clip_spans(spans, 0.75, 3.5)) == pytest.approx(
        1.25)
    records = [{"trace": (0.0, 1.0), "lower": (1.0, 2.0), "backend": None},
               {"trace": None, "lower": (5.0, 6.0), "backend": (6.0, 9.0)}]
    assert tr.compile_spans(records) == [
        (0.0, 1.0), (1.0, 2.0), (5.0, 6.0), (6.0, 9.0)]


def test_hetu_import_is_recorded_once():
    rec = next(r for r in tr.import_records() if r["name"] == tr.IMPORT)
    assert tr.IMPORT == "hetu.import"
    assert rec["dur_s"] > 0
    assert rec["dur_s"] == pytest.approx(rec["end"] - rec["start"])
    assert tr._T0_PERF >= rec["start"] and rec["end"] <= time.perf_counter()
    assert isinstance(rec["jax_preloaded"], bool)
    tr.note_import(tr.IMPORT, 0.0, False)       # a second hand-in is ignored
    assert [r for r in tr.import_records() if r["name"] == tr.IMPORT] == [rec]
    assert tr.compile_log_stats()["listening"] is True
    from jax._src import monitoring
    spans = monitoring.get_event_time_span_listeners()
    assert sum(cb == tr._LOG.on_span for cb in spans) == 1


def test_hetu_import_says_whether_jax_came_first():
    """... and the kernels' deferred import (Pallas) is a record of its own,
    made by the first thing that uses a kernel, not by the package."""
    code = ("import sys\n{first}import hetu_tpu\n"
            "from hetu_tpu.telemetry import tracing\n"
            "(r,) = tracing.import_records()\n"
            "print(r['name'], r['jax_preloaded'], r['dur_s'] > 0,"
            " tracing._LISTENING, 'jax.experimental.pallas' in sys.modules)\n"
            "import hetu_tpu.kernels\n"
            "r, k = tracing.import_records()\n"
            "print(k['name'], k['jax_preloaded'], k['start'] >= r['end'],"
            " k['dur_s'] > 0,"
            " 'jax.experimental.pallas' in sys.modules)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    for first, want in (("import jax\n", "True"), ("", "False")):
        out = subprocess.run(
            [sys.executable, "-c", code.format(first=first)], env=env,
            capture_output=True, text=True, timeout=120, check=True)
        assert out.stdout.split("\n")[-3:-1] == [
            f"hetu.import {want} True True False",
            "hetu.import.kernels False True True True"]


def test_a_jax_without_the_hooks_raises_instead_of_hearing_nothing(
        monkeypatch):
    from jax import monitoring
    monkeypatch.setattr(tr, "_LISTENING", False)
    monkeypatch.delattr(monitoring, "register_event_time_span_listener")
    with pytest.raises(AttributeError):
        tr._listen()
    assert tr.compile_log_stats()["listening"] is False


def test_tracing_stays_importable_without_jax():
    """The launcher's parent and bin/hetutrail load the telemetry files by
    path and must not pay a jax import: without jax there is no listener."""
    code = ("import importlib.util, sys\n"
            f"spec = importlib.util.spec_from_file_location('t', {os.path.join(ROOT, 'hetu_tpu', 'telemetry', 'tracing.py')!r})\n"
            "m = importlib.util.module_from_spec(spec)\n"
            "sys.modules['t'] = m\n"
            "spec.loader.exec_module(m)\n"
            "print('jax' in sys.modules, m._LISTENING, m.compile_log(),"
            " m.import_records())\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60, check=True)
    assert out.stdout.strip() == "False False [] []"


@pytest.fixture
def telemetry_dir(tmp_path, monkeypatch):
    from hetu_tpu import telemetry
    telemetry.shutdown()
    monkeypatch.setenv("HETU_TELEMETRY_DIR", str(tmp_path))
    yield str(tmp_path)
    telemetry.shutdown()


def test_the_executors_compile_ms_is_the_compile(telemetry_dir):
    from hetu_tpu import telemetry
    x = ht.Variable(name="x", trainable=False)
    w = ht.Variable("w_startup", value=np.ones((3, 2), np.float32))
    ex = ht.Executor([ht.matmul_op(x, w)], ctx=ht.cpu(0), telemetry="trace")
    sub = ex.subexecutors["default"]
    feed = {x: np.ones((4, 3), np.float32)}
    t0 = time.perf_counter()
    ex.run("default", feed_dict=feed)
    t1 = time.perf_counter()
    first = dict(sub.last_phases)
    ex.run("default", feed_dict=feed)
    second = dict(sub.last_phases)
    programs = [r for r in tr.compile_log(since=t0, until=t1)]
    assert programs, "the first step compiled its program"
    jax_ms = 1e3 * tr.span_union(tr.compile_spans(programs))
    # hetu.build plus jax's trace, lowering and compile inside hetu.dispatch
    assert first["compile_ms"] >= jax_ms > 0
    assert first["compile_ms"] <= first["step_ms"]
    assert "compile_ms" not in second

    telemetry.get().flush()
    recs = [json.loads(line) for line in
            open(os.path.join(telemetry_dir, "metrics-r0.jsonl"))]
    written = [r for r in recs if r.get("kind") == "compile"]
    assert ({r["fun_name"] for r in programs}
            <= {r["fun_name"] for r in written})
    for r in written:
        assert "sub" not in r and r["cache"] in ("hit", "miss", None)
        assert {"trace_s", "lower_s", "backend_s", "cache_read_s", "thread",
                "trace", "lower", "backend"} <= set(r)
    events = [e for e in json.load(open(os.path.join(
        telemetry_dir, "trace-r0.json")))["traceEvents"] if e.get("ph") == "X"]
    compile_, = [e for e in events if e["name"] == "compile"]
    computes = [e for e in events if e["name"] == "compute"]
    assert not any("includes_compile" in (e.get("args") or {})
                   for e in computes)
    # the span covers the build and jax's compile; compute starts where it
    # ends on that step
    assert compile_["dur"] >= 1e3 * jax_ms
    assert computes[0]["ts"] == pytest.approx(
        compile_["ts"] + compile_["dur"], abs=1.0)


def test_two_executors_write_each_program_once(telemetry_dir):
    """One record a program, however many Executors share the process's
    telemetry, and user code's programs too: written once, by `Telemetry`."""
    from hetu_tpu import telemetry
    x = ht.Variable(name="x", trainable=False)
    w = ht.Variable("w_startup_two", value=np.ones((3, 2), np.float32))
    feed = {x: np.ones((4, 3), np.float32)}
    first = ht.Executor([ht.matmul_op(x, w)], ctx=ht.cpu(0),
                        telemetry="metrics")
    second = ht.Executor([ht.matmul_op(x, w) * 2.0], ctx=ht.cpu(0),
                         telemetry="metrics")
    first.run("default", feed_dict=feed)
    second.run("default", feed_dict=feed)
    _named("startup_not_an_executors")(jnp.ones(3))
    first.run("default", feed_dict=feed)
    second.run("default", feed_dict=feed)
    telemetry.get().flush()
    written = [r for r in map(json.loads, open(os.path.join(
        telemetry_dir, "metrics-r0.jsonl"))) if r.get("kind") == "compile"]
    assert len(written) >= 3
    assert len({(r["fun_name"], tuple(r["lower"] or ()),
                 tuple(r["backend"] or ())) for r in written}) == len(written)
    assert len([r for r in written
                if r["fun_name"] == "jit(startup_not_an_executors)"]) == 1


# -- the one-device train step compiles once (PR 58) ---------------------------

@functools.cache
def _tiny_decoder(**moe):
    """(jitted init, a NEW step a call, the batch, the step's name)."""
    from hetu_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                n_layers=1, d_ff=64, max_seq_len=16, **moe)
    tok = np.arange(32, dtype=np.int32).reshape(2, 16) % 64

    def init(key):
        params = tfm.init_params(key, cfg)
        return params, tfm.init_opt_state(params)
    return jax.jit(init), (lambda: tfm.make_train_step(cfg)), (tok, tok), \
        "<lambda>"


@functools.cache
def _tiny_bert(finetune=False):
    from hetu_tpu.models import bert
    cfg = bert.BertConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=1,
                          d_ff=64, max_seq_len=16)
    B, T, P = 2, 16, 4
    batch = {"input_ids": np.ones((B, T), np.int32),
             "input_mask": np.ones((B, T), np.float32),
             "segment_ids": np.zeros((B, T), np.int32)}
    if finetune:
        batch["label"] = np.zeros((B,), np.int32)
    else:
        batch.update(mlm_positions=np.ones((B, P), np.int32),
                     mlm_ids=np.ones((B, P), np.int32),
                     mlm_weights=np.ones((B, P), np.float32),
                     nsp_label=np.zeros((B,), np.int32))

    def init(key):
        params = (bert.init_classifier_params(key, cfg, 2) if finetune
                  else bert.init_params(key, cfg))
        return params, bert.init_opt_state(params)
    make = ((lambda: bert.make_finetune_step(cfg)) if finetune
            else (lambda: bert.make_pretrain_step(cfg)))
    return jax.jit(init), make, (batch,), "step"


STEPS = {"dense": _tiny_decoder,
         "experts": lambda: _tiny_decoder(n_experts=4, n_experts_per_tok=2),
         "bert_pretrain": _tiny_bert,
         "bert_finetune": lambda: _tiny_bert(finetune=True)}
# how a caller may have made the state it hands the step
STATES = {
    # `params, opt = jax.jit(init)(key)`: what every adapter and example does
    "loose": lambda state, device: state,
    "held": lambda state, device: jax.device_put(state, device),
    # a checkpoint read into numpy
    "host": lambda state, device: jax.tree.map(np.asarray, state),
}


def _step_records(since, name):
    return [r for r in _by_name(tr.compile_log(), name) if r["seq"] > since]


@pytest.mark.parametrize("model,state_made", [
    ("dense", "loose"), ("dense", "held"), ("dense", "host"),
    ("experts", "loose"), ("experts", "held"),
    ("bert_pretrain", "loose"), ("bert_pretrain", "held"),
    ("bert_finetune", "loose")])
def test_the_one_device_step_is_lowered_and_compiled_once(model, state_made):
    """Three calls, the first on the state as the caller made it beside a
    `device_put` batch and the later ones on the step's own (committed)
    outputs, are ONE program: one record of the step's name, one lowering.
    Plain `jax.jit(step, donate_argnums=(0, 1))` leaves two from `loose`
    and `host`. The state is donated whatever committed it."""
    init, make, batch, name = STEPS[model]()
    device = jax.devices()[0]
    state = STATES[state_made](init(jax.random.PRNGKey(0)), device)
    step = make()
    since = tr.compile_count()
    losses = []
    for _ in range(3):
        given = jax.tree.leaves(state)
        out = step(*state, *jax.device_put(batch, device))
        losses.append(float(out[0]))
        state = out[-2:]
        # the caller's own handles went with the buffers
        assert all(x.is_deleted() for x in given if isinstance(x, jax.Array))
    (rec,) = _step_records(since, name)
    assert rec["lower_s"] > 0 and rec["backend_s"] > 0
    assert all(x.committed for x in jax.tree.leaves(state))
    assert losses[2] < losses[0]        # and it trains


@pytest.mark.parametrize("model", sorted(STEPS))
def test_committing_the_state_copies_nothing(model):
    from hetu_tpu.models import transformer as tfm
    init, _, batch, _ = STEPS[model]()
    state = init(jax.random.PRNGKey(0))
    leaves = jax.tree.leaves(state)
    assert not any(x.committed for x in leaves)
    device = jax.devices()[0]
    args = tfm._commit_state((*state, *jax.device_put(batch, device)))
    held = jax.tree.leaves(args[:2])
    assert all(x.committed and x.devices() == {device} for x in held)
    # a new handle on the SAME buffer: 8.6-10 GiB of state is never copied
    assert ([x.unsafe_buffer_pointer() for x in held]
            == [x.unsafe_buffer_pointer() for x in leaves])
    # and committed state is handed on as the very objects it was
    assert tfm._commit_state(args) is args


def test_loose_state_follows_the_batch_to_its_device():
    """The device is the one the call's committed arguments are on, not the
    default one; committed state is never moved (jit's own error says so)."""
    init, make, batch, name = STEPS["dense"]()
    there = jax.devices()[3]
    step = make()
    state = init(jax.random.PRNGKey(0))
    since = tr.compile_count()
    for _ in range(2):
        loss, *state = step(*state, *jax.device_put(batch, there))
    assert loss.devices() == {there}
    assert len(_step_records(since, name)) == 1
    with pytest.raises(ValueError, match="incompatible devices"):
        step(*jax.device_put(state, jax.devices()[1]),
             *jax.device_put(batch, there))


def test_nothing_committed_is_left_to_jit():
    """Uncommitted state beside an uncommitted batch is one program already
    (jit keeps the outputs uncommitted): the arguments go through as given,
    and the default device stays jit's to resolve."""
    from hetu_tpu.models import transformer as tfm
    init, make, batch, name = STEPS["dense"]()
    state = init(jax.random.PRNGKey(0))
    args = (*state, *batch)
    assert tfm._commit_state(args) is args
    step = make()
    since = tr.compile_count()
    for _ in range(2):
        loss, *state = step(*state, *batch)
    assert not loss.committed
    assert len(_step_records(since, name)) == 1


@pytest.mark.parametrize("model", sorted(STEPS))
def test_the_step_lowers_on_shapes_to_the_jits_own_text(model):
    """`.lower` is `jax.jit`'s own, on the arguments as given: on shapes it
    is the text a plain jit of the step gives (no argument carries a
    sharding, the state's are donated), under the function's own name. The
    pinned digests of `test_lfm2_model.py`, unedited, say so for the real
    cells."""
    init, make, batch, name = STEPS[model]()
    shapes = jax.eval_shape(lambda: (*init(jax.random.PRNGKey(0)), *batch))
    step = make()
    lowered = step.lower(*shapes)
    text = lowered.as_text()
    # S12(e) would name the lambda; every trace reader matches on this one
    assert text.startswith({"<lambda>": "module @jit__lambda ",
                            "step": "module @jit_step "}[name])
    head = text[text.index("func.func public @main("):].split("\n", 1)[0]
    n_state = len(jax.tree.leaves(shapes[:2]))
    assert head.count("tf.aliasing_output") == n_state
    assert "sharding" not in head
    assert step.eval_shape(*shapes)[0].shape == ()      # the jit's own too
