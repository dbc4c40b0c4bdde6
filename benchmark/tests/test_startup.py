"""CPU tests of the start-up metrics (PR 51): `reduce/startup.py` on a
hand-written log, the seven manifest entries, and their readers on a
harness run object. No number here is a device number."""
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import main, manifest       # noqa: E402
from benchmark.harness.spans import Spans           # noqa: E402
from benchmark.reduce import startup                # noqa: E402
from benchmark.tests.test_benchmark import (           # noqa: E402,F401
    _add, _last_line, _toy_cell, on_cpu, root)

STARTUP_METRICS = {"setup_" + key: key for key in startup.METRICS}


def _record(name, thread, trace, lower, backend, cache):
    parts = {"trace": trace, "lower": lower, "backend": backend}
    rec = {"fun_name": f"jit({name})", "thread": thread, "cache": cache,
           "cache_read_s": 0.5 if cache == "hit" else 0.0, **parts}
    for part, span in parts.items():
        rec[part + "_s"] = span[1] - span[0] if span else 0.0
    rec["end"] = max(span[1] for span in parts.values() if span)
    return rec


# one thread: the weights' init misses, an eager program compiles INSIDE the
# step's trace (a nested pair: its three parts lie in the step's trace span)
# and hits, the step hits, a helper never asks the cache; the check's
# program ends after `until`. A second thread compiles one program meanwhile
LOG = [
    _record("init", 1, (10.0, 11.0), (11.0, 12.0), (12.0, 20.0), "miss"),
    _record("iota", 1, (30.5, 30.6), (30.6, 30.8), (30.8, 31.8), "hit"),
    _record("step", 1, (30.0, 34.0), (34.0, 36.0), (36.0, 39.0), "hit"),
    _record("helper", 1, (40.0, 40.25), (40.25, 40.5), (40.5, 41.5), None),
    _record("other_thread", 2, (30.0, 31.0), (31.0, 32.0), (32.0, 33.0),
            "miss"),
    _record("check", 1, (60.0, 61.0), (61.0, 62.0), (62.0, 70.0), "miss"),
]
# the package's import before anything else; the kernels' (Pallas) inside
# the step's trace, where a model that defers it pays it
IMPORTS = [{"name": "hetu.import", "start": 1.0, "end": 1.75, "dur_s": 0.75,
            "jax_preloaded": True},
           {"name": "hetu.import.kernels", "start": 32.0, "end": 33.5,
            "dur_s": 1.5, "jax_preloaded": False}]
# two warm-up steps, the second Executor build (the helper) inside them, one
# read; then the traced window's steps and its read; the measured window
SPANS = {"feed": [(38.5, 38.6), (39.5, 39.6), (46.0, 46.1), (50.0, 50.1)],
         "step_call": [(38.6, 39.4), (39.6, 42.0), (46.1, 46.2),
                       (50.1, 50.2)],
         "sync": [(42.0, 45.0), (46.2, 47.0), (50.2, 51.0)]}
UNTIL = 50.0


def test_reduce_startup_on_a_hand_written_log():
    got = startup.reduce_startup(LOG, IMPORTS, SPANS, UNTIL)
    assert set(got) == set(startup.METRICS)
    assert got["import_s"] == 0.75 + 1.5
    # thread 1: init 2 + step 6 (the nested program's trace and lowering lie
    # inside the step's trace: once) + helper 0.5, less the second the nested
    # program's backend span covers of the step's trace and the 1.5 of the
    # kernels' import inside it; thread 2: 2
    assert got["trace_lower_s"] == pytest.approx(
        2 + 6 + 0.5 - 1.0 - 1.5 + 2)
    plain_sum = sum(r["trace_s"] + r["lower_s"] for r in LOG[:5])
    assert got["trace_lower_s"] < plain_sum == pytest.approx(10.8)
    # init's 8, the helper's 1 (it did not ask), the other thread's 1
    assert got["compile_s"] == pytest.approx(8 + 1 + 1)
    assert got["cache_read_s"] == pytest.approx(1 + 3)
    assert got["cache_miss_programs"] == 2
    assert got["programs"] == 5
    # 38.5 to the first read's end at 45.0, less the step's backend span
    # from 38.5 to 39.0 and the helper's 1.5
    assert got["warmup_steps_s"] == pytest.approx(6.5 - 0.5 - 1.5)
    # the books of one thread close: nothing is counted twice
    one = [r for r in LOG if r["thread"] == 1]
    got = startup.reduce_startup(one, [], {}, UNTIL)
    assert (got["trace_lower_s"] + got["compile_s"] + got["cache_read_s"]
            == pytest.approx(startup.total(startup.union(
                [r[p] for r in one[:4] for p in ("trace", "lower",
                                                 "backend")]))))
    assert got["import_s"] == 0.0 and got["warmup_steps_s"] == 0.0


def test_reduce_startup_cuts_the_log_where_setup_ends():
    early = startup.reduce_startup(LOG, IMPORTS, SPANS, 25.0)
    assert early["programs"] == 1 and early["cache_miss_programs"] == 1
    # an import that ends after the cut (the check's first kernel) is out
    assert early["import_s"] == 0.75
    assert early["cache_read_s"] == 0.0 and early["compile_s"] == 8.0
    # no read of the loss has ended yet: no warm-up to speak of
    assert early["warmup_steps_s"] == 0.0
    late = startup.reduce_startup(LOG, IMPORTS, SPANS, 100.0)
    assert late["programs"] == 6 and late["cache_miss_programs"] == 3
    assert late["compile_s"] == pytest.approx(8 + 1 + 1 + 8)
    # the warm-up stays the first steps to the FIRST read
    assert late["warmup_steps_s"] == pytest.approx(4.5)


def test_the_warm_up_is_the_first_step_to_the_first_read_after_it():
    assert startup.warm_up_window(SPANS, UNTIL) == (38.5, 45.0)
    executor = {"run_call": [(5.0, 6.0), (6.0, 7.0)],
                "sync": [(1.0, 2.0), (7.0, 9.0), (12.0, 13.0)]}
    # a read before the first step (none today) is not the warm-up's
    assert startup.warm_up_window(executor, 20.0) == (5.0, 9.0)
    assert startup.warm_up_window(executor, 8.0) is None
    assert startup.warm_up_window({}, 20.0) is None
    assert startup.warm_up_window({"sync": [(1.0, 2.0)]}, 20.0) is None


def test_the_seven_startup_entries_resolve_for_all_eleven_cells():
    m = manifest.load(ROOT)
    cells = [w["name"] for w in m["workloads"]]
    entries = [p for p in m["per_layer"] if p["name"] in STARTUP_METRICS]
    assert [p["name"] for p in entries] == list(STARTUP_METRICS)
    # appended: nothing that was there moved
    assert m["per_layer"][-7:] == entries
    for p in entries:
        assert p["moves"] == "setup_s" and p["better"] == "lower"
        assert p["layer"] == "entry points"
        assert p["unit"] == ("count" if p["name"].endswith("programs")
                             else "s")
        assert p["source"] == ("program_counter" if p["unit"] == "count"
                               else "program_span")
        # a list, so a later cell is appended, never implied
        assert p["workloads"][:11] == cells[:11] and len(cells) >= 11
    for name in cells[:11]:
        cell = manifest.resolve(ROOT, name)
        assert set(STARTUP_METRICS) <= {p["name"] for p in cell.per_layer}
        for metric in STARTUP_METRICS:
            assert callable(manifest.reader(cell, metric).read)
    # `setup_s` was the one end-to-end metric no layer metric moved
    moved = {p["moves"] for p in m["per_layer"]}
    assert moved == {e["name"] for e in m["end_to_end"]}


def _run(records=None, sync_times=(50.0, 60.0)):
    spans = Spans(enabled=False)
    spans.records = dict(records or {})
    return {"spans": spans,
            "window": types.SimpleNamespace(sync_times=list(sync_times))}


def test_no_startup_reader_returns_none_on_an_empty_log(monkeypatch):
    from hetu_tpu.telemetry import tracing
    monkeypatch.setattr(tracing, "_LOG", tracing.CompileLog())
    monkeypatch.setattr(tracing, "_IMPORTS", [])
    cell = manifest.resolve(ROOT, "wdl-criteo.local-table-bs128")
    got = {name: manifest.reader(cell, name).read(_run())
           for name in STARTUP_METRICS}
    # 0.0 and 0: no traced line ever lacks a listed metric
    assert got == {name: 0 for name in STARTUP_METRICS}
    assert all(isinstance(got[n], int) == n.endswith("programs")
               for n in got)


def test_startup_readers_read_the_programs_own_log(monkeypatch):
    from hetu_tpu.telemetry import tracing
    log = tracing.CompileLog()
    monkeypatch.setattr(tracing, "_LOG", log)
    monkeypatch.setattr(tracing, "_IMPORTS", [dict(IMPORTS[0])])
    unix = tracing._T0_UNIX - tracing._T0_PERF
    for name, base, event in (("init", 10.0, tracing.EV_CACHE_MISS),
                              ("step", 20.0, tracing.EV_CACHE_HIT),
                              ("check", 70.0, tracing.EV_CACHE_MISS)):
        log.on_span(tracing.EV_TRACE, unix + base, unix + base + 1,
                    fun_name=name)
        log.on_span(tracing.EV_LOWER, unix + base + 1, unix + base + 2,
                    fun_name=f"jit({name})")
        log.on_event(tracing.EV_CACHE_ASKED)
        log.on_event(event)
        log.on_span(tracing.EV_BACKEND, unix + base + 2, unix + base + 5,
                    fun_name=f"jit({name})")
    cell = manifest.resolve(ROOT, "bert-base.pretrain-seq512")
    run = _run({"feed": [(24.0, 24.5)], "sync": [(25.0, 30.0)]})
    got = {name: manifest.reader(cell, name).read(run)
           for name in STARTUP_METRICS}
    assert got == pytest.approx({
        "setup_import_s": 0.75, "setup_trace_lower_s": 4.0,
        "setup_compile_s": 3.0, "setup_cache_read_s": 3.0,
        "setup_cache_miss_programs": 1, "setup_programs": 2,
        "setup_warmup_steps_s": 6.0 - 1.0}, abs=1e-4)


def test_startup_readers_return_nothing_for_a_program_without_the_log(
        monkeypatch):
    """The parent of the PR that added the log: the metric is left out of
    the line and nothing raises."""
    from hetu_tpu.telemetry import tracing
    monkeypatch.delattr(tracing, "compile_log")
    cell = manifest.resolve(ROOT, "bert-base.pretrain-seq512")
    for name in STARTUP_METRICS:
        assert manifest.reader(cell, name).read(_run()) is None


def test_startup_readers_return_nothing_when_jax_does_not_reach_the_log(
        monkeypatch):
    """A log that hears nothing would read as a start-up that compiled
    nothing: the line leaves the metric out, and the driver refuses it."""
    from hetu_tpu.telemetry import tracing
    assert tracing.compile_log_stats()["listening"] is True
    monkeypatch.setattr(tracing, "_LISTENING", False)
    cell = manifest.resolve(ROOT, "bert-base.pretrain-seq512")
    for name in STARTUP_METRICS:
        assert manifest.reader(cell, name).read(_run()) is None


def test_a_traced_run_prints_the_seven_startup_metrics(root, on_cpu, capsys,   # noqa: F811
                                                       monkeypatch):
    from benchmark.reduce import trace
    _toy_cell(root)
    _add(root, metrics=[
        ({"name": name + ".toy", "unit": "s", "better": "lower",
          "source": "program_span", "layer": "toy", "moves": "setup_s",
          "workloads": ["toy.toy-mix"]}, None) for name in STARTUP_METRICS])
    with open(os.path.join(HERE, "fixtures", "trace_two_chips.json")) as f:
        raw = json.load(f)
    monkeypatch.setattr(trace, "read_xplane", lambda path: raw)
    import time
    t0 = time.perf_counter()
    rc = main.main(["--workload", "toy.toy-mix", "--seed", "3",
                    "--seconds", "0.1", "--trace", "1"],
                   root=str(root), t0=t0)
    assert rc == 0
    line = _last_line(capsys)
    got = {k[:-4]: v["value"] for k, v in line["metrics"].items()
           if k.startswith("setup_")}
    assert set(got) == set(STARTUP_METRICS)
    # the toy job compiles nothing: two 1 ms steps and a read are the warm-up
    assert 0.002 <= got["setup_warmup_steps_s"] < 0.5
    assert got["setup_warmup_steps_s"] <= line["phases"][
        "compile_and_warm_up_s"]
    assert got["setup_import_s"] > 0
    assert got["setup_cache_miss_programs"] <= got["setup_programs"]
