"""hetuprof (hetu_tpu/telemetry/profiler.py, docs/PROFILING.md):

- HLO op_name metadata parsing and scope extraction (jvp/transpose
  wrappers resolve backward work to its forward op)
- per-op attribution over a SYNTHETIC Chrome trace: lane filtering via
  trace metadata, interval-union wall time, collective bucketing, step
  normalization from hetu_step annotations
- named_scope presence in the executor's optimized HLO; the cached
  compiled-executable handle; ``last_memory_analysis``
- HBM/params/6ND telemetry gauges under ``JAX_PLATFORMS=cpu``
- the perf-regression gate's exit-code contract for {clean, regressed,
  incomplete-baseline, incomplete-current} + the ``--gate --check`` CLI
- parity of ``DEVICE_PEAKS`` / ``mfu`` with ``benchmark/reduce/peaks.py``
- hetutop's dual-denominator MFU columns
"""
import gzip
import json
import os
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from hetu_tpu.telemetry import profiler as prof  # noqa: E402


# ---------------------------------------------------------------------------
# HLO metadata parsing + scope extraction
# ---------------------------------------------------------------------------

HLO_SAMPLE = """\
HloModule jit_step_fn
%fused_computation (p: f32[16,8]) -> f32[16,8] {
  ROOT %maximum.1 = f32[16,8] maximum(...), metadata={op_name="jit(step_fn)/jit(main)/Relu_6/max" source_file="x.py"}
}
ENTRY %main {
  %dot.1 = f32[16,8] dot(...), metadata={op_name="jit(step_fn)/jit(main)/MatMul_5/dot_general"}
  %fusion.2 = f32[16,8] fusion(...), kind=kLoop, metadata={op_name="jit(step_fn)/jit(main)/Gradient(w)/transpose(Gradient(w))/jvp(Relu_6)/max"}
  ROOT %all-reduce.3 = f32[16,8] all-reduce(...), metadata={op_name="jit(step_fn)/jit(main)/AllReduce_9/psum"}
}
"""


def test_hlo_op_map_parses_instructions():
    m = prof.hlo_op_map(HLO_SAMPLE)
    assert m["dot.1"].endswith("MatMul_5/dot_general")
    assert "jvp(Relu_6)" in m["fusion.2"]
    assert "maximum.1" in m and "all-reduce.3" in m


def test_scope_of_resolves_wrappers_to_forward_op():
    known = {"MatMul_5", "Relu_6", "Gradient(w)", "AllReduce_9"}
    op, bwd = prof.scope_of("jit(step_fn)/jit(main)/MatMul_5/dot_general",
                            known)
    assert (op, bwd) == ("MatMul_5", False)
    # backward work resolves to the INNERMOST op, not the Gradient node
    op, bwd = prof.scope_of(
        "jit(step_fn)/jit(main)/Gradient(w)/transpose(Gradient(w))/"
        "jvp(MatMul_5)/transpose", known)
    assert (op, bwd) == ("MatMul_5", True)
    # without a known set, hetu-shaped names (<Name>_<id>) are accepted
    op, _ = prof.scope_of("jit(f)/jit(main)/SoftmaxCrossEntropy_17/mul")
    assert op == "SoftmaxCrossEntropy_17"
    assert prof.scope_of("jit(f)/jit(main)/reduce_sum", known) == (None, False)


# ---------------------------------------------------------------------------
# synthetic-trace attribution
# ---------------------------------------------------------------------------

def _synthetic_events():
    """Two Eigen worker lanes + one python host lane, two annotated steps.
    dot.1 runs as two OVERLAPPING slices (parallel workers): total 200 us
    but wall-union 150 us."""
    meta = [
        {"ph": "M", "pid": 7, "name": "process_name",
         "args": {"name": "/host:CPU"}},
        {"ph": "M", "pid": 7, "tid": 11, "name": "thread_name",
         "args": {"name": "tf_XLAEigen/11"}},
        {"ph": "M", "pid": 7, "tid": 12, "name": "thread_name",
         "args": {"name": "tf_XLAEigen/12"}},
        {"ph": "M", "pid": 7, "tid": 20, "name": "thread_name",
         "args": {"name": "python"}},
    ]
    evs = [
        {"ph": "X", "pid": 7, "tid": 11, "ts": 0, "dur": 100,
         "name": "dot.1"},
        {"ph": "X", "pid": 7, "tid": 12, "ts": 50, "dur": 100,
         "name": "dot.1"},
        {"ph": "X", "pid": 7, "tid": 11, "ts": 200, "dur": 50,
         "name": "fusion.2"},
        {"ph": "X", "pid": 7, "tid": 12, "ts": 300, "dur": 40,
         "name": "all-reduce.3"},
        # host-lane python work must NOT count as device time
        {"ph": "X", "pid": 7, "tid": 20, "ts": 0, "dur": 5000,
         "name": "shard_args"},
        {"ph": "X", "pid": 7, "tid": 20, "ts": 0, "dur": 400,
         "name": "hetu_step"},
        {"ph": "X", "pid": 7, "tid": 20, "ts": 500, "dur": 400,
         "name": "hetu_step"},
        # an unmapped device event lands in a visible <bucket>
        {"ph": "X", "pid": 7, "tid": 11, "ts": 400, "dur": 30,
         "name": "copy.9"},
    ]
    return meta + evs


OP_MAP = {
    "dot.1": "jit(step_fn)/jit(main)/MatMul_5/dot_general",
    "fusion.2": "jit(step_fn)/jit(main)/Gradient(w)/"
                "transpose(Gradient(w))/jvp(Relu_6)/max",
    "all-reduce.3": "jit(step_fn)/jit(main)/AllReduce_9/psum",
}
KNOWN = {"MatMul_5", "Relu_6", "Gradient(w)", "AllReduce_9"}


def test_attribute_synthetic_trace():
    att = prof.attribute(_synthetic_events(), op_map=OP_MAP,
                         known_ops=KNOWN)
    assert att.steps == 2   # from the hetu_step annotations
    rows = att.rows
    assert rows["MatMul_5"].total_us == 200
    assert rows["MatMul_5"].wall_us == 150      # overlap merged
    assert rows["MatMul_5"].count == 2
    assert rows["Relu_6"].bwd_us == 50          # via jvp/transpose wrappers
    assert rows["all-reduce.3"].family == "<collective>"
    assert att.collective_wall_us == 40
    assert "<copy>" in rows                      # unmapped but visible
    assert "shard_args" not in rows              # host lane excluded
    assert att.unattributed_us == 30
    assert 0 < att.attributed_fraction < 1
    table = att.table()
    assert "MatMul_5" in table and "us/step" in table
    d = att.as_dict()
    assert d["steps"] == 2 and d["ops"][0]["op"] == "MatMul_5"


def test_attribute_without_lane_metadata_falls_back_to_name_shape():
    evs = [e for e in _synthetic_events() if e["ph"] == "X"]
    att = prof.attribute(evs, op_map=OP_MAP, known_ops=KNOWN, steps=2)
    # no metadata: HLO-shaped lowercase names pass, PascalCase host
    # TraceMe names would not — shard_args unfortunately matches the
    # shape, which is exactly why real traces use lane metadata; here we
    # assert the mapped ops still resolve
    assert att.rows["MatMul_5"].total_us == 200
    assert att.steps == 2


def test_trace_file_roundtrip(tmp_path):
    run = tmp_path / "plugins" / "profile" / "run1"
    run.mkdir(parents=True)
    p = run / "host.trace.json.gz"
    with gzip.open(p, "wt") as f:
        json.dump({"traceEvents": _synthetic_events()}, f)
    files = prof.find_xla_traces(str(tmp_path))
    assert files == [str(p)]
    evs = prof.load_trace_events(files[0])
    assert any(e.get("name") == "dot.1" for e in evs)


# ---------------------------------------------------------------------------
# executor integration: named_scope, cached executable, memory analysis
# ---------------------------------------------------------------------------

def _tiny_mlp(ht):
    x = ht.Variable(name="x", trainable=False)
    y_ = ht.Variable(name="y_", trainable=False)
    w = ht.init.random_normal((8, 4), stddev=0.1, name="w")
    loss = ht.reduce_mean_op(
        ht.softmaxcrossentropy_op(ht.matmul_op(x, w), y_), [0])
    opt = ht.optim.SGDOptimizer(0.1)
    return x, y_, loss, opt.minimize(loss)


def _run_steps(ex, x, y_, n=2, bs=16):
    rng = np.random.RandomState(0)
    for _ in range(n):
        xv = rng.randn(bs, 8).astype(np.float32)
        yv = np.eye(4, dtype=np.float32)[rng.randint(0, 4, bs)]
        ex.run("train", feed_dict={x: xv, y_: yv})


def test_named_scope_lands_in_optimized_hlo():
    import hetu_tpu as ht
    x, y_, loss, train_op = _tiny_mlp(ht)
    ex = ht.Executor({"train": [loss, train_op]}, ctx=ht.cpu(0), seed=0)
    _run_steps(ex, x, y_)
    sub = ex.subexecutors["train"]
    txt = sub.dump_hlo(stage="optimized")
    op_names = [n.name for n in sub.topo
                if not (n.is_placeholder or n.is_dataloader)]
    hit = [n for n in op_names if n in txt]
    # the heavy hitters must be navigable; tiny ops may fuse away entirely
    assert any(n.startswith("MatMul") for n in hit), (hit, op_names)
    assert any("Optimizer" in n for n in hit), hit
    # ... and the map parses back out of the text
    m = prof.hlo_op_map(txt)
    assert any("MatMul" in path for path in m.values())


def test_executable_cache_and_memory_analysis():
    import hetu_tpu as ht
    x, y_, loss, train_op = _tiny_mlp(ht)
    ex = ht.Executor({"train": [loss, train_op]}, ctx=ht.cpu(0), seed=0)
    _run_steps(ex, x, y_)
    sub = ex.subexecutors["train"]
    e1 = sub._executable()
    e2 = sub._executable()
    assert e1 is e2 and len(sub._exe_cache) == 1   # one fetch per signature
    cost = sub.last_cost_analysis()
    assert cost and cost.get("flops", 0) > 0
    mem = sub.last_memory_analysis()
    assert mem is not None
    for k in ("argument_bytes", "output_bytes", "temp_bytes",
              "alias_bytes", "peak_bytes"):
        assert k in mem and mem[k] >= 0, (k, mem)
    assert mem["peak_bytes"] == (mem["argument_bytes"] + mem["output_bytes"]
                                 + mem["temp_bytes"] - mem["alias_bytes"])
    # a second signature gets its own cached handle
    _run_steps(ex, x, y_, n=1, bs=32)
    sub._executable()
    assert len(sub._exe_cache) == 2


def test_memory_and_6nd_gauges_under_cpu(tmp_path, monkeypatch):
    from hetu_tpu import telemetry
    telemetry.shutdown()
    monkeypatch.delenv("HETU_TELEMETRY", raising=False)
    monkeypatch.setenv("HETU_TELEMETRY_DIR", str(tmp_path / "tel"))
    import hetu_tpu as ht
    x, y_, loss, train_op = _tiny_mlp(ht)
    ex = ht.Executor({"train": [loss, train_op]}, ctx=ht.cpu(0), seed=0,
                     telemetry="metrics")
    _run_steps(ex, x, y_, n=3)
    snap = ex.telemetry.metrics.snapshot()
    assert snap["hetu_params_total"] == 32            # the 8x4 weight
    assert snap["hetu_flops_per_step_6nd"] == 6.0 * 32 * 16
    assert snap["hetu_hbm_peak_bytes"] > 0
    assert snap["hetu_hbm_argument_bytes"] > 0
    mem = ex.subexecutors["train"].last_memory_analysis()
    assert snap["hetu_hbm_peak_bytes"] == mem["peak_bytes"]
    telemetry.shutdown()


def test_xla_trace_window_advertised_in_jsonl(tmp_path, monkeypatch):
    from hetu_tpu import telemetry
    telemetry.shutdown()
    monkeypatch.setenv("HETU_TELEMETRY_DIR", str(tmp_path / "tel"))
    monkeypatch.setenv("HETU_XLA_TRACE", str(tmp_path / "xla") + ":5:3")
    tel = telemetry.activate("metrics")
    tel.flush()
    recs = [json.loads(l) for l in
            open(tmp_path / "tel" / "metrics-r0.jsonl")]
    w = [r for r in recs if r.get("kind") == "xla_trace"]
    assert w and w[0]["start_step"] == 5 and w[0]["n_steps"] == 3
    telemetry.shutdown()


# ---------------------------------------------------------------------------
# the perf-regression gate
# ---------------------------------------------------------------------------

GOOD = {"detail": {"a": {"samples_per_sec": 100.0, "step_ms": 10.0},
                   "b": {"mfu_6nd": 0.3, "tokens_per_sec": 5000.0}},
        "value": 100.0}


def _gate(base, cur, tol=10.0):
    bc, bm = prof.normalize_summary(base)
    cc, cm = prof.normalize_summary(cur)
    return prof.gate(bc, cc, tol, baseline_meta=bm, current_meta=cm)


def test_gate_clean_on_identical_rerun():
    res = _gate(GOOD, GOOD)
    assert res.status == prof.GATE_OK and not res.regressions
    assert res.compared == 4


def test_gate_regressed_on_slowed_current():
    slow = json.loads(json.dumps(GOOD))
    slow["detail"]["a"]["samples_per_sec"] = 70.0   # -30% < -10% tol
    slow["detail"]["a"]["step_ms"] = 14.3
    res = _gate(GOOD, slow)
    assert res.status == prof.GATE_REGRESSED
    cells = {r["cell"] for r in res.regressions}
    assert cells == {"a"}
    assert "REGRESSED" in res.report()
    # within tolerance: clean (and an improvement is not a regression)
    ok = json.loads(json.dumps(GOOD))
    ok["detail"]["a"]["samples_per_sec"] = 95.0     # -5% within tol
    ok["detail"]["b"]["tokens_per_sec"] = 9000.0    # improvement
    res = _gate(GOOD, ok)
    assert res.status == prof.GATE_OK
    assert res.improvements and not res.regressions


def test_gate_incomplete_current_never_reads_as_win_or_loss():
    part = {"detail": {"a": GOOD["detail"]["a"],
                       "b": {"error": "rc=124: backend died"}},
            "value": 100.0, "incomplete_cells": ["b"]}
    res = _gate(GOOD, part)
    assert res.status == prof.GATE_INCOMPLETE_CURRENT
    assert res.incomplete == ["b"] and not res.regressions


def test_gate_incomplete_baseline_distinct_code():
    dead = {"detail": {"a": {"error": "skipped: backend unresponsive"}},
            "value": None}
    assert _gate(dead, GOOD).status == prof.GATE_INCOMPLETE_BASELINE
    # the driver's wrapper form of a dead round: rc=124, parsed null
    wrapper = {"n": 5, "cmd": "x", "rc": 124, "parsed": None}
    bc, bm = prof.normalize_summary(wrapper)
    assert bc == {} and bm["incomplete"]


def test_gate_files_and_cli(tmp_path):
    base = tmp_path / "base.json"
    cur = tmp_path / "cur.json"
    base.write_text(json.dumps(GOOD))
    cur.write_text(json.dumps(GOOD))
    res = prof.gate_files(str(base), str(cur))
    assert res.status == prof.GATE_OK
    # unreadable current/baseline -> the matching incomplete code
    assert prof.gate_files(str(base), str(tmp_path / "nope.json")).status \
        == prof.GATE_INCOMPLETE_CURRENT
    assert prof.gate_files(str(tmp_path / "nope.json"), str(cur)).status \
        == prof.GATE_INCOMPLETE_BASELINE
    env = {**os.environ, "PYTHONPATH": REPO}
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "hetuprof"),
         "--gate", "--check"], env=env, capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "incomplete-baseline -> exit 3 ok" in r.stdout
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "hetuprof"),
         "--gate", str(base), "--current", str(cur)],
        env=env, capture_output=True, text=True)
    assert r.returncode == 0 and "clean" in r.stdout


# ---------------------------------------------------------------------------
# roofline
# ---------------------------------------------------------------------------

def test_roofline_classifies_op_families():
    import hetu_tpu as ht
    x = ht.Variable(name="x", value=np.zeros((512, 512), np.float32),
                    trainable=False)
    w = ht.init.random_normal((512, 2048), stddev=0.1, name="w")
    h = ht.relu_op(ht.matmul_op(x, w))
    rows = prof.roofline_rows([h], training=False)
    by_fam = {r.family: r for r in rows}
    assert "MatMul" in by_fam and "Relu" in by_fam
    mm = by_fam["MatMul"]
    assert mm.flops == 2.0 * 512 * 2048 * 512
    assert mm.bound in ("compute", "memory")
    # relu is pure traffic: memory-bound at any realistic ridge
    assert by_fam["Relu"].bound == "memory"
    assert by_fam["Relu"].intensity < mm.intensity
    txt = prof.format_roofline(rows)
    assert "MatMul" in txt and "ridge" in txt


def test_roofline_joins_measured_times():
    import hetu_tpu as ht
    x = ht.Variable(name="x", shape=(16, 8), trainable=False)
    w = ht.init.random_normal((8, 4), stddev=0.1, name="w")
    out = ht.matmul_op(x, w)
    att = prof.attribute(_synthetic_events(), op_map={
        "dot.1": f"jit(f)/jit(main)/{out.name}/dot_general"},
        known_ops={out.name})
    rows = prof.roofline_rows([out], training=False, attribution=att)
    mm = next(r for r in rows if r.family == "MatMul")
    assert mm.measured_us == pytest.approx(150 / 2)   # wall/steps
    assert mm.residual is not None and mm.residual > 0


# ---------------------------------------------------------------------------
# the benchmark's copy of the peaks (ROADMAP D13)
# ---------------------------------------------------------------------------

def test_device_peaks_parity_with_benchmark():
    """`benchmark/reduce/peaks.py` copied this module's table; until one
    copy goes, a chip named by both has the same peaks in both, and the
    two utilization formulas agree."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_benchmark_peaks",
        os.path.join(REPO, "benchmark", "reduce", "peaks.py"))
    peaks = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(peaks)
    shared = set(prof.DEVICE_PEAKS) & set(peaks.DEVICE_PEAKS)
    assert "TPU v5 lite" in shared
    for kind in shared:
        for key in ("tflops", "gbs"):
            assert prof.DEVICE_PEAKS[kind][key] \
                == peaks.DEVICE_PEAKS[kind][key], (kind, key)
        # BERT-base at seq 512: 6ND + attention FLOPs a token, 4 chips
        flops_per_token = 6.0 * 110e6 + prof.attn_flops(
            1, 512, 12, 768, False) / 512
        tokens_per_s, chips, step_s = 450_300.0, 4, 0.581
        flops_per_chip_step = tokens_per_s * step_s * flops_per_token / chips
        assert prof.mfu(flops_per_chip_step, step_s, kind) == pytest.approx(
            peaks.utilization(tokens_per_s, flops_per_token, chips, kind))
    assert prof.attn_flops(32, 512, 12, 768, True) \
        == prof.attn_flops(32, 512, 12, 768, False) / 2.0


# ---------------------------------------------------------------------------
# hetutop dual-denominator MFU + profile_dir
# ---------------------------------------------------------------------------

def test_hetutop_reports_both_mfu_denominators(tmp_path):
    from hetu_tpu.telemetry import hetutop
    d = tmp_path / "tel"
    d.mkdir()
    n_params, tokens = 110_000_000, 32 * 512
    f6 = 6.0 * n_params * tokens
    recs = [
        {"kind": "run_info", "ts": 1.0, "rank": 0,
         "device_kind": "fake-v5e", "peak_tflops": 197.0},
        {"kind": "model_info", "ts": 1.0, "rank": 0, "n_layers": 12,
         "d_model": 768, "seq_len": 512, "causal": False,
         "n_params": n_params},
        {"kind": "step", "ts": 2.0, "rank": 0, "sub": "train", "step": 1,
         "step_ms": 215.0,
         "metrics": {"hetu_flops_per_step_6nd": f6,
                     "hetu_params_total": float(n_params)}},
    ]
    (d / "metrics-r0.jsonl").write_text(
        "".join(json.dumps(r) + "\n" for r in recs))
    state = hetutop.gather(str(d))
    mfu6, mfu_a = hetutop._mfu_pair(
        state["ranks"][0]["metrics"], state["model"], 215.0, 197.0)
    # docs/ROOFLINE.md BERT numbers: ~25% 6ND, ~28% attention-inclusive
    assert mfu6 == pytest.approx(25.5, abs=1.0)
    assert mfu_a > mfu6   # attention add-on raises utilization
    assert mfu_a == pytest.approx(mfu6 * 1.086, rel=0.02)
    frame = hetutop.render_frame(state)
    assert "MFU6nd%" in frame and "MFUatt%" in frame
    # without model geometry the attention column falls back to the
    # measured cost-analysis gauge
    m = {"hetu_flops_per_step_6nd": f6, "hetu_flops_per_step": f6 * 1.1}
    mfu6b, mfu_ab = hetutop._mfu_pair(m, {}, 215.0, 197.0)
    assert mfu_ab == pytest.approx(mfu6b * 1.1, rel=1e-6)


def test_profile_dir_reports_partial_as_partial(tmp_path):
    d = tmp_path / "tel"
    d.mkdir()
    (d / "metrics-r0.jsonl").write_text("")
    rep = prof.profile_dir(str(d))
    assert rep["breakdown"] is None
    assert any("no step records" in w for w in rep["incomplete"])
    assert any("trace" in w for w in rep["incomplete"])


def test_profile_executor_end_to_end(tmp_path, monkeypatch):
    """The acceptance path (docs/PROFILING.md): a real executor run under
    telemetry=trace with a bounded HETU_XLA_TRACE window -> per-op time
    table attributing >= 85% of observed device time to graph ops (the
    'within 15% of the measured compute span' criterion), with backward
    shares and the exact HLO join."""
    from hetu_tpu import telemetry
    import hetu_tpu as ht
    monkeypatch.delenv("HETU_TELEMETRY", raising=False)

    def traced_run(root):
        telemetry.shutdown()
        monkeypatch.setenv("HETU_TELEMETRY_DIR", str(root / "tel"))
        monkeypatch.setenv("HETU_XLA_TRACE", str(root / "xla") + ":2:3")
        x, y_, loss, train_op = _tiny_mlp(ht)
        ex = ht.Executor({"train": [loss, train_op]}, ctx=ht.cpu(0), seed=0,
                         telemetry="trace")
        # a batch large enough that graph-op work, not the ~1 us thunks
        # copying scalar constants into the outputs, fills the step
        _run_steps(ex, x, y_, n=7, bs=8192)
        telemetry.get().flush()
        return prof.profile_executor(ex, "train")["attribution"]

    # jax 0.9.0's CPU profiler leaves the client's worker threads out of
    # some sessions altogether (about one in four here, and every time the
    # test runs alone in a fresh process): such a session has no per-op
    # event to join, so the window is taken again
    for attempt in range(4):
        att = traced_run(tmp_path / str(attempt))
        if att.rows:
            break
    assert att.steps == 3                      # the configured window
    assert att.rows and att.device_wall_us > 0
    matmul = [r for r in att.rows.values() if r.family == "MatMul"]
    assert matmul and matmul[0].bwd_us > 0     # backward work resolved
    assert att.attributed_fraction >= 0.85, att.table()
    telemetry.shutdown()


def test_cli_attr_mode_smoke(tmp_path):
    """bin/hetuprof over a synthetic telemetry dir + trace window."""
    tel = tmp_path / "tel"
    tel.mkdir()
    xla = tmp_path / "xla" / "plugins" / "profile" / "r1"
    xla.mkdir(parents=True)
    with gzip.open(xla / "h.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": _synthetic_events()}, f)
    recs = [
        {"kind": "xla_trace", "ts": 1.0, "rank": 0,
         "dir": str(tmp_path / "xla"), "start_step": 0, "n_steps": 2},
        {"kind": "step", "ts": 2.0, "rank": 0, "sub": "train", "step": 1,
         "step_ms": 2.0, "phases": {"prestep_ms": 0.5, "dispatch_ms": 1.0,
                                    "poststep_ms": 0.5}},
    ]
    (tel / "metrics-r0.jsonl").write_text(
        "".join(json.dumps(r) + "\n" for r in recs))
    env = {**os.environ, "PYTHONPATH": REPO}
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "hetuprof"), str(tel)],
        env=env, capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "per-step breakdown" in r.stdout
    assert "<dot>" in r.stdout   # no HLO given: base-name buckets
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "hetuprof"), str(tel),
         "--json"], env=env, capture_output=True, text=True)
    rep = json.loads(r.stdout)
    assert rep["attribution"]["steps"] == 2
