"""CPU tests of the benchmark's harness (seconds): `pytest benchmark/tests`.
No number here is a device number; the platform check is patched to let
the adapters run at tiny sizes under the CPU pin."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import device, main, manifest  # noqa: E402
from benchmark.reduce import flops, peaks, trace      # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture
def root(tmp_path):
    """A checkout of the benchmark alone, to which a test adds files."""
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    return tmp_path


@pytest.fixture
def on_cpu(monkeypatch):
    monkeypatch.setattr(device, "PLATFORM", "cpu")


def _add(root, *, config=None, traffic=None, cell=None, metrics=()):
    """Add entries and files only, as a later PR may."""
    m = json.loads((root / "BENCHMARK.json").read_text())
    if config:
        name, files = config
        d = root / "benchmark" / "configs" / name
        d.mkdir()
        for fname, text in files.items():
            (d / fname).write_text(text)
        m["configs"].append({
            "name": name, "source": "test", "reduced": [], "why": "test",
            "file": f"benchmark/configs/{name}/config.json"})
    if traffic:
        name, params = traffic
        (root / "benchmark" / "traffic" / f"{name}.json").write_text(
            json.dumps(params))
    if cell:
        m["workloads"].append(cell)
        for e in m["end_to_end"]:
            if e["name"] in ("samples_per_s",):
                e["workloads"].append(cell["name"])
    for entry, text in metrics:
        m["per_layer"].append(entry)
        if text:
            (root / "benchmark" / "layer_metrics" /
             f"{entry['name'].split('.')[0]}.py").write_text(text)
    (root / "BENCHMARK.json").write_text(json.dumps(m))


TOY_ADAPTER = '''
import time
def build(config, traffic, seed, devices, batches, spans):
    return Toy(spans, batches)
class Toy:
    items_per_step = 4
    def __init__(self, spans, batches):
        self.spans, self.n, self.batches = spans, 0, batches
    def step(self):
        with self.spans("run_call"):
            time.sleep(0.001); self.n += 1
    def sync(self):
        with self.spans("sync"):
            return 1.0 / (1 + self.n)
    def counters(self): return {"steps": self.n}
    def check(self, reference): return {"ok": reference.ANSWER == 42}
    def close(self): self.closed = True
'''
TOY_GENERATOR = "def generate(traffic, config, seed):\n    return [seed]\n"
TOY_READER = "def read(run):\n    return run['counters']['steps']\n"


def _toy_cell(root):
    (root / "benchmark" / "generators" / "toy_gen.py").write_text(
        TOY_GENERATOR)
    _add(root,
         config=("toy", {"config.json": "{}", "adapter.py": TOY_ADAPTER,
                         "reference.py": "ANSWER = 42\n"}),
         traffic=("toy-mix", {"generator": "toy_gen", "sync_every": 5,
                              "throughput_metric": "samples_per_s",
                              "warmup_steps": 2, "trace_steps": 7}),
         cell={"name": "toy.toy-mix", "config": "toy", "traffic": "toy-mix",
               "chips": 1, "why": "test"},
         metrics=[({"name": "toy_steps", "unit": "count", "better": "higher",
                    "source": "program_counter", "layer": "toy",
                    "moves": "samples_per_s",
                    "workloads": ["toy.toy-mix"]}, TOY_READER),
                  ({"name": "run_call_ms_p50.toy", "unit": "ms",
                    "better": "lower", "source": "program_span",
                    "layer": "toy", "moves": "samples_per_s",
                    "workloads": ["toy.toy-mix"]}, None),
                  ({"name": "mfu_pct.toy", "unit": "%", "better": "higher",
                    "source": "host_clock", "layer": "toy",
                    "moves": "samples_per_s",
                    "workloads": ["toy.toy-mix"]}, None)])


def _last_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


# -- the manifest -----------------------------------------------------------

def test_manifest_names_units_and_files():
    m = _manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in m[k]]
    for n in names + [w["traffic"] for w in m["workloads"]]:
        assert NAME.match(n), n
    metric_names = [e["name"] for e in m["end_to_end"] + m["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    cells = {w["name"] for w in m["workloads"]}
    e2e = {e["name"]: e for e in m["end_to_end"]}
    for e in m["end_to_end"] + m["per_layer"]:
        assert UNIT.match(e["unit"]), e
        assert e["better"] in ("lower", "higher")
        assert e["source"] in SOURCES
        assert set(e.get("workloads", cells)) <= cells
    for e in m["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.1
        assert e["source"] in ("host_clock", "device_trace")
    assert "setup_s" in e2e
    for p in m["per_layer"]:
        moved = e2e[p["moves"]]
        assert set(p.get("workloads", cells)) <= set(
            moved.get("workloads", cells)), p["name"]
    four = [w for w in m["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    assert {w["config"] for w in m["workloads"]} == {
        c["name"] for c in m["configs"]}
    assert len(json.dumps(m)) < 64 * 1024
    for w in m["workloads"]:
        cell = manifest.resolve(ROOT, w["name"])
        assert cell.traffic["throughput_metric"] in {
            e["name"] for e in cell.end_to_end}
        assert cell.per_layer, w["name"]
        for p in cell.per_layer:
            assert hasattr(manifest.reader(cell, p["name"]), "read")
        assert hasattr(manifest.adapter(cell), "build")
        assert hasattr(manifest.generator(cell), "generate")
        assert len(cell.traffic["why"]) > 20


def test_every_shipped_traffic_file_names_a_generator():
    d = os.path.join(ROOT, "benchmark", "traffic")
    for f in os.listdir(d):
        with open(os.path.join(d, f)) as fh:
            t = json.load(fh)
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "generators", t["generator"] + ".py")), f
        assert t["sync_every"] >= 1 and t["trace_steps"] >= 1


# -- the harness -------------------------------------------------------------

def test_cell_added_as_new_files_only(root, on_cpu, capsys):
    _toy_cell(root)
    rc = main.main(["--workload", "toy.toy-mix", "--seed", "3",
                    "--seconds", "0.2", "--trace", "0"],
                   root=str(root), t0=__import__("time").perf_counter())
    assert rc == 0
    line = _last_line(capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 50
    assert set(line["metrics"]) == {"samples_per_s", "setup_s"}
    assert line["metrics"]["samples_per_s"]["unit"] == "samples/s"
    # 1 ms a step, 4 items a step: a little under 4000 a second
    assert 1000 < line["metrics"]["samples_per_s"]["value"] < 4000
    assert line["device"]["platform"] == "cpu"
    assert line["window"]["compiles"] == 0
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}


def test_traced_run_reports_per_layer_metrics(root, on_cpu, capsys,
                                              monkeypatch):
    _toy_cell(root)
    with open(os.path.join(HERE, "fixtures", "trace_two_chips.json")) as f:
        raw = json.load(f)
    monkeypatch.setattr(trace, "read_xplane", lambda path: raw)
    rc = main.main(["--workload", "toy.toy-mix", "--seed", "3",
                    "--seconds", "0.1", "--trace", "1"],
                   root=str(root), t0=0.0)
    assert rc == 0
    line = _last_line(capsys)
    # mfu_pct has no flops_per_item to read: left out of the line
    assert set(line["metrics"]) == {"toy_steps", "run_call_ms_p50.toy"}
    assert 1.0 <= line["metrics"]["run_call_ms_p50.toy"]["value"] < 5.0
    assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
    assert len(line["breakdown"]["device_ops"]) <= 10
    assert line["breakdown"]["idle_gaps"][0][0] == "sync"


class _DeviceBound:
    """Steps return at once and queue 10 ms of device time each; a read
    waits until the queue has drained."""
    items_per_step = 1

    def __init__(self):
        self.free_at = 0.0

    def step(self):
        import time
        self.free_at = max(time.perf_counter(), self.free_at) + 0.01

    def sync(self):
        import time
        time.sleep(max(0.0, self.free_at - time.perf_counter()))
        return 1.0


def test_window_closes_where_the_device_will_be_not_where_the_host_is():
    from benchmark.harness import window
    job = _DeviceBound()
    window.warm_up(job, 2)
    win = window.run(job, seconds=0.25, sync_every=10)
    # 10 ms a step: ~25 steps; closing on the host's clock alone would let
    # a full 10 steps queue up behind the 0.25 s mark
    assert 0.25 <= win.seconds < 0.30, win
    assert win.steps == win.attempted and 24 <= win.steps <= 29
    assert win.steps_per_s == pytest.approx(100, rel=0.1)
    assert win.failed == 0 and len(win.sync_times) >= 3


def test_window_counts_steps_with_a_loss_that_is_not_finite():
    from benchmark.harness import window
    job = _DeviceBound()
    job.sync = lambda: float("nan")
    win = window.run(job, seconds=0.0, sync_every=4, max_steps=8)
    assert (win.attempted, win.failed, win.steps) == (8, 8, 0)


def test_unknown_workload_and_missing_file_fail(root, capsys):
    assert main.main(["--workload", "nope", "--seed", "0", "--seconds", "1",
                      "--trace", "0"], root=str(root), t0=0.0) == 1
    assert "no workload 'nope'" in capsys.readouterr().err
    os.remove(root / "benchmark" / "traffic" / "pretrain-seq512.json")
    assert main.main(["--workload", "bert-base.pretrain-seq512", "--seed",
                      "0", "--seconds", "1", "--trace", "0"],
                     root=str(root), t0=0.0) == 1


def test_no_tpu_fails_and_names_the_platform():
    """The command itself, as the driver starts it, in this sandbox."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "bert-base.pretrain-seq512", "--seed", "0", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert "'cpu'" in p.stderr and "no result" in p.stderr
    assert p.stdout.strip() == ""


def test_too_few_chips_fails(on_cpu):
    with pytest.raises(device.DeviceError, match="asks for 64 chip"):
        device.require(64)


# -- the yardstick ------------------------------------------------------------

@pytest.fixture
def reduced():
    with open(os.path.join(HERE, "fixtures", "trace_two_chips.json")) as f:
        raw = json.load(f)
    assert os.path.getsize(f.name) < 200 * 1024
    lines = {ln["name"] for p in raw["planes"] for ln in p["lines"]}
    assert {"Steps", "XLA Modules", "XLA Ops", "Async XLA Ops"} <= lines
    return trace.reduce_trace(raw, ["feed", "step_call", "sync"])


def test_trace_reducer_busy_and_idle_from_the_ops_line_only(reduced):
    # window: host spans 1000..11000 ns. chip 0 busy 6700 ns, chip 1 9000;
    # the Steps (10000 ns) and XLA Modules (8500 ns) events would make
    # chip 0 busy from 1000 to 11000 if they were counted
    assert reduced["window_s"] == pytest.approx(10000e-9)
    c0, c1 = reduced["chips"]
    assert c0["busy_s"] == pytest.approx(6700e-9)
    assert c1["busy_s"] == pytest.approx(9000e-9)
    assert c0["idle_pct"] == pytest.approx(33.0)
    assert c1["idle_pct"] == pytest.approx(10.0)
    assert reduced["busy_s"] == pytest.approx(7850e-9)


def test_trace_reducer_collectives_kernels_and_gaps(reduced):
    c0 = reduced["chips"][0]
    # all-reduce: async span 8000..9600; fusion.4 hides 8500..9000 of it
    assert c0["collective_s"] == pytest.approx(1600e-9)
    assert c0["collective_exposed_s"] == pytest.approx(1100e-9)
    assert reduced["chips"][1]["collective_s"] == 0
    # the while's body ops are not counted twice: self times add to busy
    assert c0["ops_self_s"] == pytest.approx(6700e-9)
    assert c0["mosaic_s"] == pytest.approx(1000e-9)
    ops = dict(reduced["device_ops"])
    assert ops["mosaic:custom-call"] == pytest.approx(1000 / 2e9)
    assert ops["fusion"] == pytest.approx((2000 + 1500 + 500 + 9000) / 2e9)
    assert ops["while"] == pytest.approx(1500 / 2e9)
    # chip 0 is the idlest: 1000 ns before the first op while the host
    # feeds, 2300 ns while it waits in sync
    assert dict(reduced["idle_gaps"]) == pytest.approx(
        {"sync": 2300e-9, "feed": 1000e-9})


def test_trace_reducer_refuses_a_trace_without_device_ops():
    with pytest.raises(ValueError, match="no /device:TPU"):
        trace.reduce_trace({"planes": [{"name": "/host:CPU", "lines": []}]})


def test_interval_arithmetic():
    assert trace.union([(5, 7), (1, 3), (2, 4)]) == [(1, 4), (5, 7)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert trace.family("%fusion.12.3") == "fusion"
    assert trace.family("all-reduce-start.4") == "all-reduce-start"
    assert trace.family("checkpoint.19", trace.MOSAIC) == "mosaic:checkpoint"
    assert trace.is_collective("%all-gather.1")
    assert trace.is_collective("%ar.1", "all-reduce")
    assert not trace.is_collective("%fusion.1", "fusion")


def test_parse_op_reads_the_instruction_text_of_a_tpu_trace():
    """Names as the v5e's trace has them (my chip run, PR 22)."""
    assert trace.parse_op(
        "%while.7 = (s32[]{:T(128)}, bf16[64,512,768]{2,1,0:T(8,128)(2,1)}) "
        "while((s32[]{:T(128)}) %tuple.1), condition=%c, body=%b") == (
            "while.7", "while")
    assert trace.parse_op(
        "%Optimizer_SGDOptimizer_37.5 = f32[4194304,128]{1,0:T(8,128)} "
        "custom-call(f32[4194304,128]{1,0:T(8,128)} %copy.26), "
        'custom_call_target="tpu_custom_call", operand_layout_constraints={}'
    ) == ("Optimizer_SGDOptimizer_37.5", trace.MOSAIC)
    # an operand that is a custom call's result does not make a kernel
    assert trace.parse_op(
        "%fusion.641 = bf16[3072]{0:T(1024)(128)(2,1)S(1)} fusion(bf16[3072,"
        "768]{1,0:T(8,128)(2,1)S(1)} %custom-call.28), kind=kOutput") == (
            "fusion.641", "fusion")
    assert trace.parse_op("%all-reduce.3 = f32[768]{0:T(1024)} all-reduce("
                          "f32[768]{0:T(1024)} %x), replica_groups={}") == (
                              "all-reduce.3", "all-reduce")
    assert trace.parse_op("jit_step(123)") == ("jit_step(123)", "")


def test_flops_and_peaks():
    # BERT-base at 512 tokens, 80 predictions: ~0.59 GFLOP a token
    f = flops.bert_pretrain_flops_per_token(768, 12, 3072, 30522, 512, 80)
    assert 5.8e8 < f < 6.0e8
    # 6ND over the 85 M trunk parameters is the larger part of it
    assert f > 6 * 85e6
    assert peaks.utilization(1e5, f, 1, "TPU v5 lite") == pytest.approx(
        1e5 * f / 197e12)
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("cpu")


# -- the adapters, at tiny sizes --------------------------------------------

def _shrink(root, config, traffic, cfg_edit, traffic_edit):
    p = root / "benchmark" / "configs" / config / "config.json"
    c = json.loads(p.read_text())
    c.update(cfg_edit)
    p.write_text(json.dumps(c))
    p = root / "benchmark" / "traffic" / f"{traffic}.json"
    t = json.loads(p.read_text())
    t.update(traffic_edit)
    p.write_text(json.dumps(t))


@pytest.mark.parametrize("traffic", ["pretrain-seq512",
                                     "pretrain-seq512-dp4"])
def test_bert_adapter_runs_and_agrees_with_reference(root, on_cpu, capsys,
                                                     traffic):
    _shrink(root, "bert-base", traffic,
            {"hidden_size": 64, "num_hidden_layers": 2,
             "num_attention_heads": 4, "intermediate_size": 128,
             "vocab_size": 512, "max_position_embeddings": 64},
            {"sequences": 8, "seq_len": 32, "predictions": 5,
             "sync_every": 2})
    rc = main.main(["--workload", "bert-base." + traffic, "--seed", "1",
                    "--seconds", "0.5", "--trace", "0"],
                   root=str(root), t0=0.0)
    assert rc == 0
    line = _last_line(capsys)
    assert line["correct"], line["check"]
    assert line["check"]["hidden_rel_rms_err"] < 2.5e-2
    assert line["window"]["compiles"] == 0
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}


@pytest.mark.parametrize("traffic", ["local-table-bs128", "hybrid-ps-bs128"])
def test_wdl_adapter_runs_and_agrees_with_reference(root, on_cpu, capsys,
                                                    traffic):
    _shrink(root, "wdl-criteo", traffic, {"table_rows": 20000},
            {"batches": 8})
    if traffic.startswith("hybrid"):
        _add(root, cell={"name": "wdl-criteo.hybrid", "config": "wdl-criteo",
                         "traffic": traffic, "chips": 1, "why": "test"})
        cell = "wdl-criteo.hybrid"
    else:
        cell = "wdl-criteo." + traffic
    rc = main.main(["--workload", cell, "--seed", "1", "--seconds", "0.5",
                    "--trace", "0"], root=str(root), t0=0.0)
    assert rc == 0
    line = _last_line(capsys)
    assert line["correct"], line["check"]
    assert line["check"]["rows_touched"] > 1000
    # exact float32 on the CPU: the reference's arithmetic is the program's
    assert line["check"]["dense_rel_err"] < 1e-3, line["check"]
    assert line["check"]["rows_rel_err"] < 1e-3, line["check"]
    assert not line["check"]["untouched_rows_moved"]
    if traffic.startswith("hybrid"):
        acct = line["check"]["accounting"]
        assert acct["client_pushes_ok"] == acct["server_updates"] > 0
        from hetu_tpu.ps import local_cluster
        assert not local_cluster.get_live_cluster()   # reaped with the job
