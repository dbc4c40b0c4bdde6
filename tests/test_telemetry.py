"""Telemetry subsystem (hetu_tpu/telemetry, docs/OBSERVABILITY.md):

- tracer spans nest and flush to valid Chrome-trace JSON (Perfetto schema)
- histogram percentile math and the Prometheus textfile exposition format
- the per-step JSONL records validate under ``hetutop --check``; per-rank
  traces merge into rank lanes and validate under ``hetutrace --check``
  (both CLIs smoke-tested as subprocesses, the CI pattern)
- an instrumented Executor run produces step records with phases; the
  graphboard timings overlay renders from them
- ``telemetry="off"`` (the default) leaves the hot path with ZERO
  instrument calls — asserted by patching every metric/trace mutator
- PS RPC counters + extended kServerStats under a live ``local_cluster``
- satellite regressions: AUC NaN-on-degenerate, bench telemetry line,
  heturun run summary, PSSupervisor stats export
"""
import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def fresh_telemetry(tmp_path, monkeypatch):
    """Isolated telemetry singleton: clean env, tmp output dir, and a
    guaranteed shutdown so no other test inherits an active instance."""
    from hetu_tpu import telemetry
    telemetry.shutdown()
    monkeypatch.delenv("HETU_TELEMETRY", raising=False)
    monkeypatch.setenv("HETU_TELEMETRY_DIR", str(tmp_path / "tel"))
    yield str(tmp_path / "tel")
    telemetry.shutdown()


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_span_nesting_flush_valid_chrome_json(tmp_path):
    from hetu_tpu.telemetry.tracing import Tracer
    path = str(tmp_path / "trace.json")
    tr = Tracer(path, rank=3)
    with tr.span("outer", args={"step": 1}):
        with tr.span("inner"):
            pass
    tr.instant("marker", args={"k": "v"})
    tr.flush()
    doc = json.load(open(path))
    evs = doc["traceEvents"]
    assert isinstance(evs, list)
    spans = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert set(spans) == {"outer", "inner"}
    for e in spans.values():
        for k in ("ts", "dur", "pid", "tid"):
            assert k in e, (e, k)
        assert e["pid"] == 3
    # nesting: inner lies within outer on the same lane
    o, i = spans["outer"], spans["inner"]
    assert o["tid"] == i["tid"]
    assert o["ts"] <= i["ts"] and i["ts"] + i["dur"] <= o["ts"] + o["dur"]
    # process_name metadata gives the rank lane its label
    meta = [e for e in evs if e["ph"] == "M" and e["name"] == "process_name"]
    assert meta and meta[0]["args"]["name"] == "rank 3"
    assert any(e["ph"] == "i" and e["name"] == "marker" for e in evs)


def test_tracer_file_always_valid_midrun(tmp_path):
    """flush_every causes periodic rewrites; the on-disk file must be valid
    JSON after every flush (crash durability for the resilience paths)."""
    from hetu_tpu.telemetry.tracing import Tracer
    path = str(tmp_path / "t.json")
    tr = Tracer(path, rank=0, flush_every=2)
    for i in range(5):
        with tr.span(f"s{i}"):
            pass
    doc = json.load(open(path))  # auto-flushed at 2-span boundaries
    assert any(e.get("ph") == "X" for e in doc["traceEvents"])


def test_xla_trace_window_spec_parsing():
    from hetu_tpu.telemetry.tracing import XlaTraceWindow
    w = XlaTraceWindow("/tmp/xla:100:5")
    assert (w.dir, w.start_step, w.n_steps) == ("/tmp/xla", 100, 5)
    w2 = XlaTraceWindow("/tmp/xla")
    assert (w2.start_step, w2.n_steps) == (0, 10)
    # the annotation is usable as a context manager with or without jax
    from hetu_tpu.telemetry.tracing import step_span
    with step_span(7):
        pass


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_histogram_percentile_math():
    from hetu_tpu.telemetry.registry import Histogram
    h = Histogram("t_ms")
    for v in range(1, 101):   # 1..100
        h.observe(float(v))
    assert h.count == 100 and h.min == 1.0 and h.max == 100.0
    assert h.mean == pytest.approx(50.5)
    assert h.percentile(50) == pytest.approx(50.0, abs=1.0)
    assert h.percentile(99) == pytest.approx(99.0, abs=1.0)
    assert h.percentile(0) == 1.0 and h.percentile(100) == 100.0
    # cumulative bucket counts are monotone and end at count
    cum, total = 0, []
    for n in h.bucket_counts:
        cum += n
        total.append(cum)
    assert total[-1] == h.count
    assert Histogram("empty").percentile(50) is None


def test_prometheus_textfile_format(tmp_path):
    from hetu_tpu.telemetry.registry import MetricsRegistry
    reg = MetricsRegistry()
    reg.counter("hetu_steps_total").inc(3)
    reg.gauge("hetu_flops_per_step", {"sub": "train"}).set(1e9)
    h = reg.histogram("hetu_step_time_ms", buckets=(1, 10))
    h.observe(0.5)
    h.observe(5.0)
    h.observe(50.0)
    text = reg.to_prometheus()
    lines = text.splitlines()
    assert "# TYPE hetu_steps_total counter" in lines
    assert "hetu_steps_total 3" in lines
    assert '# TYPE hetu_flops_per_step gauge' in lines
    assert 'hetu_flops_per_step{sub="train"} 1e+09' in lines
    assert 'hetu_step_time_ms_bucket{le="1"} 1' in lines
    assert 'hetu_step_time_ms_bucket{le="10"} 2' in lines
    assert 'hetu_step_time_ms_bucket{le="+Inf"} 3' in lines
    assert "hetu_step_time_ms_count 3" in lines
    assert any(l.startswith("hetu_step_time_ms_sum ") for l in lines)
    # atomic textfile write
    p = reg.write_prometheus(str(tmp_path / "m.prom"))
    assert open(p).read() == text


def test_registry_snapshot_flat_scalars():
    from hetu_tpu.telemetry.registry import MetricsRegistry
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.histogram("h").observe(2.0)
    snap = reg.snapshot()
    assert snap["c"] == 1.0
    assert snap["h_count"] == 1 and snap["h_p50"] == 2.0
    assert all(isinstance(v, (int, float)) for v in snap.values())


def test_type_conflict_raises():
    from hetu_tpu.telemetry.registry import MetricsRegistry
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")


# ---------------------------------------------------------------------------
# executor integration + CLIs
# ---------------------------------------------------------------------------

def _tiny_mlp(ht):
    x = ht.Variable(name="x", trainable=False)
    y_ = ht.Variable(name="y_", trainable=False)
    w = ht.init.random_normal((8, 2), stddev=0.1, name="w")
    loss = ht.reduce_mean_op(
        ht.softmaxcrossentropy_op(ht.matmul_op(x, w), y_), [0])
    opt = ht.optim.SGDOptimizer(0.1)
    return x, y_, loss, opt.minimize(loss)


def _feeds(rng, bs=16):
    return (rng.randn(bs, 8).astype(np.float32),
            np.eye(2, dtype=np.float32)[rng.randint(0, 2, bs)])


def test_executor_trace_end_to_end(fresh_telemetry, tmp_path):
    import hetu_tpu as ht
    from hetu_tpu import telemetry
    from hetu_tpu.telemetry import hetutop, hetutrace
    x, y_, loss, train_op = _tiny_mlp(ht)
    ex = ht.Executor({"train": [loss, train_op], "eval": [loss]},
                     ctx=ht.cpu(0), seed=0, telemetry="trace")
    assert ex.telemetry is not None and ex.config.telemetry == "trace"
    rng = np.random.RandomState(0)
    for _ in range(6):
        xv, yv = _feeds(rng)
        ex.run("train", feed_dict={x: xv, y_: yv})
    xv, yv = _feeds(rng)
    ex.run("eval", feed_dict={x: xv, y_: yv})
    tel = telemetry.get()
    tel.flush()

    # step records: phases + metrics, validated by the hetutop checker
    assert hetutop.check_dir(fresh_telemetry) == 0
    recs = [json.loads(l) for l in
            open(os.path.join(fresh_telemetry, "metrics-r0.jsonl"))]
    steps = [r for r in recs if r.get("kind") == "step"]
    assert len(steps) == 6   # training only; the eval run is a histogram
    assert {"prestep_ms", "dispatch_ms", "poststep_ms"} <= set(
        steps[0]["phases"])
    assert "compile_ms" in steps[0]["phases"]          # first step compiled
    assert "compile_ms" not in steps[1]["phases"]      # second did not
    # snapshots ride the cadence (step 0) + the flush-time "final" record
    assert "metrics" in steps[0] and "metrics" not in steps[1]
    finals = [r for r in recs if r.get("kind") == "final"]
    assert finals, "flush() writes a closing metrics snapshot"
    m = finals[-1]["metrics"]
    assert m["hetu_steps_total"] == 6
    assert m["hetu_examples_total"] == 6 * 16
    assert m["hetu_compiles_total"] == 1
    assert m["hetu_recompiles_total"] == 0
    # the eval run lands in the registry (it postdates the last step record)
    assert tel.metrics.snapshot()["hetu_eval_time_ms_count"] == 1
    assert any(r.get("kind") == "run_info" and "device_kind" in r
               for r in recs)

    # trace: spans for feed/compute/step phases, eval lane, valid schema
    trace_path = os.path.join(fresh_telemetry, "trace-r0.json")
    assert hetutrace.check_file(trace_path) == 0
    names = {e["name"] for e in json.load(open(trace_path))["traceEvents"]
             if e.get("ph") == "X"}
    assert {"step:train", "feed", "compile", "compute", "poststep",
            "eval:eval"} <= names

    # graphboard satellite: timings overlay renders heat + phase table
    from hetu_tpu import graphboard
    out = graphboard.render(ex, name="train",
                            out_dir=str(tmp_path / "gb"), timings=True)
    html = open(os.path.join(out, "index.html")).read()
    assert "phase timings" in html and "compute (dispatch)" in html
    svg = open(os.path.join(out, "output.svg")).read()
    assert "ms step (" in svg   # tooltip carries the phase share

    # prometheus textfile landed on flush
    prom = open(os.path.join(fresh_telemetry, "metrics-r0.prom")).read()
    assert "# TYPE hetu_step_time_ms histogram" in prom


def test_render_timings_without_telemetry_notes_absence(tmp_path):
    import hetu_tpu as ht
    from hetu_tpu import graphboard, telemetry
    telemetry.shutdown()
    x, y_, loss, train_op = _tiny_mlp(ht)
    ex = ht.Executor({"train": [loss, train_op]}, ctx=ht.cpu(0), seed=0)
    out = graphboard.render(ex, out_dir=str(tmp_path / "gb"), timings=True)
    assert "no telemetry data" in open(os.path.join(out, "index.html")).read()


def test_off_mode_adds_no_instrument_calls(tmp_path, monkeypatch):
    """The zero-overhead-off contract: with telemetry off (the default),
    a training step performs NO metric observations, counter increments,
    gauge sets, trace appends, or JSONL writes — counted by patching every
    mutator in the telemetry layer."""
    from hetu_tpu import telemetry
    from hetu_tpu.telemetry import registry as reg_mod, tracing as tr_mod
    telemetry.shutdown()
    monkeypatch.delenv("HETU_TELEMETRY", raising=False)
    calls = []
    monkeypatch.setattr(reg_mod.Histogram, "observe",
                        lambda self, v: calls.append(("observe", v)))
    monkeypatch.setattr(reg_mod.Counter, "inc",
                        lambda self, v=1.0: calls.append(("inc", v)))
    monkeypatch.setattr(reg_mod.Gauge, "set",
                        lambda self, v: calls.append(("set", v)))
    monkeypatch.setattr(reg_mod.JsonlSink, "write",
                        lambda self, rec: calls.append(("jsonl", rec)))
    monkeypatch.setattr(tr_mod.Tracer, "_append",
                        lambda self, ev: calls.append(("trace", ev)))
    # the hetu.* spans are jax's own TraceMe and are always written; the
    # perf_counter stamps beside them are taken only for a consumer
    monkeypatch.setattr(tr_mod._Stamped, "__init__",
                        lambda self, *a: calls.append(("stamp", a[1])))
    spans = []
    real_span = tr_mod.span
    monkeypatch.setattr(
        tr_mod, "span",
        lambda name, stamps=None, **kw: (spans.append((name, stamps)),
                                         real_span(name, stamps, **kw))[1])
    import hetu_tpu as ht
    x, y_, loss, train_op = _tiny_mlp(ht)
    ex = ht.Executor({"train": [loss, train_op]}, ctx=ht.cpu(0), seed=0)
    assert ex.telemetry is None and ex.config.telemetry == "off"
    rng = np.random.RandomState(0)
    for _ in range(3):
        xv, yv = _feeds(rng)
        ex.run("train", feed_dict={x: xv, y_: yv})
    assert calls == []   # instrument count: exactly zero
    assert ex.subexecutors["train"].last_phases is None
    # ... while every step still wrote its spans, none of them stamped
    assert [n for n, _s in spans] == 3 * ([tr_mod.STEP]
                                          + list(tr_mod.STEP_SPANS))
    assert all(stamps is None for _n, stamps in spans)


def test_hetutop_check_rejects_invalid(tmp_path):
    from hetu_tpu.telemetry import hetutop
    d = tmp_path / "tel"
    d.mkdir()
    assert hetutop.check_dir(str(d)) == 1           # no files
    (d / "metrics-r0.jsonl").write_text("not json\n")
    assert hetutop.check_dir(str(d)) == 1           # invalid line
    (d / "metrics-r0.jsonl").write_text(
        json.dumps({"kind": "step", "sub": "t", "step": 0}) + "\n")
    assert hetutop.check_dir(str(d)) == 1           # missing required keys
    (d / "metrics-r0.jsonl").write_text(
        json.dumps({"kind": "step", "sub": "t", "step": 0, "ts": 1.0,
                    "step_ms": 1.5, "metrics": {}}) + "\n")
    assert hetutop.check_dir(str(d)) == 0


def test_hetutrace_merge_rank_lanes(tmp_path):
    from hetu_tpu.telemetry.tracing import Tracer
    from hetu_tpu.telemetry import hetutrace
    d = tmp_path / "tel"
    for r in range(2):
        tr = Tracer(str(d / f"trace-r{r}.json"), rank=r)
        with tr.span("step"):
            pass
        tr.flush()
    out = hetutrace.merge([str(d)], str(tmp_path / "merged.json"))
    assert hetutrace.check_file(out) == 0
    evs = json.load(open(out))["traceEvents"]
    assert {e["pid"] for e in evs if e.get("ph") == "X"} == {0, 1}
    lanes = {e["args"]["name"] for e in evs
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    assert lanes == {"rank 0", "rank 1"}


def test_cli_check_smoke(tmp_path):
    """bin/hetutop --check and bin/hetutrace --check as real subprocesses
    (exit 0 on valid, 1 on invalid) — the hetulint --json CI pattern."""
    from hetu_tpu.telemetry.tracing import Tracer
    d = tmp_path / "tel"
    d.mkdir()
    (d / "metrics-r0.jsonl").write_text(
        json.dumps({"kind": "step", "sub": "t", "step": 0, "ts": 1.0,
                    "step_ms": 1.5, "metrics": {"hetu_steps_total": 1}})
        + "\n")
    tr = Tracer(str(d / "trace-r0.json"))
    with tr.span("step"):
        pass
    tr.flush()
    env = {**os.environ, "PYTHONPATH": REPO}
    rc_top = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "hetutop"),
         str(d), "--check"], env=env, capture_output=True, text=True)
    assert rc_top.returncode == 0, rc_top.stderr + rc_top.stdout
    rc_tr = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "hetutrace"), "--check",
         str(d / "trace-r0.json")], env=env, capture_output=True, text=True)
    assert rc_tr.returncode == 0, rc_tr.stderr + rc_tr.stdout
    bad = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "hetutrace"), "--check",
         str(d / "metrics-r0.jsonl")], env=env, capture_output=True,
        text=True)
    assert bad.returncode == 1


# ---------------------------------------------------------------------------
# PS RPC counters under a live local cluster
# ---------------------------------------------------------------------------

def _telemetry_ps_worker(client, rank, tmpdir):
    import os
    tel_dir = os.path.join(tmpdir, "tel")
    os.environ["HETU_TELEMETRY_DIR"] = tel_dir
    os.environ["HETU_TELEMETRY_PS_EVERY"] = "1"
    import numpy as np
    import hetu_tpu as ht
    from hetu_tpu import telemetry
    from hetu_tpu.telemetry import hetutop
    x = ht.Variable(name="x", trainable=False)
    y_ = ht.Variable(name="y_", trainable=False)
    w = ht.init.zeros((8, 1), name="w")
    err = ht.matmul_op(x, w) - y_
    loss = ht.reduce_mean_op(ht.mul_op(err, err), [0])
    opt = ht.optim.SGDOptimizer(0.05)
    train_op = opt.minimize(loss)
    ex = ht.Executor({"train": [loss, train_op]}, ctx=ht.cpu(0),
                     comm_mode="PS", telemetry="metrics")
    rng = np.random.RandomState(3)
    for _ in range(4):
        xv = rng.randn(8, 8).astype(np.float32)
        yv = (xv.sum(axis=1, keepdims=True) > 0).astype(np.float32)
        ex.run("train", feed_dict={x: xv, y_: yv})
    ex.close()
    tel = telemetry.get()
    assert tel is not None
    snap = tel.metrics.snapshot()
    # PS push latency histogram saw this run's gradient pushes
    assert snap.get("hetu_ps_push_ms_count", 0) > 0, snap
    # critical-path PS RPC share of the step (hetuprof pillar 1; the
    # executor stamps the staging-pull + push blocks on PS runs)
    assert 0 < snap.get("hetu_comm_fraction", 0) <= 1, snap
    tel.flush()
    # extended kServerStats: request count, apply latency, dedup ledger
    st = client.ServerStats(0)
    assert st["requests"] > 0
    assert st["apply_ms_avg"] is not None and st["apply_ms_avg"] >= 0
    assert st["dedup_clients"] >= 1
    assert st["snapshot_age_ms"] == -1   # no snapshot dir in this cluster
    cs = client.ClientStats()
    assert cs["rpcs"] > 0 and cs["retries"] == 0 and cs["failovers"] == 0
    # ps_server rows landed in the JSONL and the checker reads them
    assert hetutop.check_dir(tel_dir) == 0
    recs = [json.loads(l) for l in
            open(os.path.join(tel_dir, "metrics-r0.jsonl"))]
    ps_rows = [r for r in recs if r.get("kind") == "ps_server"]
    assert ps_rows and all("snapshot_age_ms" in r for r in ps_rows)


def test_ps_rpc_counters_local_cluster(tmp_path):
    from test_ps import run_cluster
    run_cluster(_telemetry_ps_worker, tmp_path, n_workers=1, n_servers=1)


def test_ps_supervisor_stats_export(tmp_path, monkeypatch):
    """PSSupervisor exports lapse/respawn counters and appends its events
    to <HETU_TELEMETRY_DIR>/ps_supervisor.jsonl."""
    monkeypatch.setenv("HETU_TELEMETRY_DIR", str(tmp_path))
    from hetu_tpu.ps.supervisor import PSSupervisor
    sup = PSSupervisor("127.0.0.1", 1, 1, respawn=lambda i: None)
    assert sup.stats() == {"lapses": 0, "respawns": 0, "max_respawns": 3,
                           "fatal": None}
    sup._note("server 0 dead; respawning")
    rec = json.loads(open(tmp_path / "ps_supervisor.jsonl").read())
    assert rec["name"] == "ps_supervisor" and "respawns" in rec
    assert "server 0 dead" in rec["message"]


# ---------------------------------------------------------------------------
# satellites
# ---------------------------------------------------------------------------

def test_auc_degenerate_inputs_nan_with_warning():
    from hetu_tpu import metrics as M
    # healthy case unchanged
    assert M.auc([0, 1, 0, 1], [0.1, 0.9, 0.2, 0.8]) > 0.99
    for labels, preds, curve in (
            ([1, 1, 1], [0.5, 0.6, 0.7], "ROC"),   # all positive
            ([0, 0, 0], [0.5, 0.6, 0.7], "ROC"),   # all negative
            ([], [], "ROC"),                        # empty
            ([0, 0], [0.1, 0.2], "PR")):            # PR without positives
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            v = M.auc(labels, preds, curve=curve)
        assert np.isnan(v), (labels, curve, v)
        assert len(w) == 1 and "undefined" in str(w[0].message)
    # PR with positives but single-class-negative is fine (defined)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        v = M.auc([1, 1], [0.6, 0.9], curve="PR")
    assert not np.isnan(v) and not w


def test_heturun_run_summary(tmp_path, monkeypatch):
    from hetu_tpu import runner
    (tmp_path / "metrics-r0.jsonl").write_text("{}\n")
    (tmp_path / "stale.tmp").write_text("")
    monkeypatch.setattr(runner, "_tel_dir", str(tmp_path))
    runner._write_telemetry_summary(0, False, 2)
    s = json.loads(open(tmp_path / "run_summary.json").read())
    assert s["workers"] == 2 and s["exit_code"] == 0
    assert s["files"] == ["metrics-r0.jsonl"]   # .tmp and itself excluded
