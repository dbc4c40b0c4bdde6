"""CPU tests of reduce/inside.py and the readers over it, on fixtures cut
from real TPU v5e traces (PR 23): `pytest benchmark/tests/test_inside.py`.
Every expected number is worked out here from the fixture's own lines."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import manifest                     # noqa: E402
from benchmark.reduce import inside, kernel_flops, peaks   # noqa: E402


def _fixture(name):
    with open(os.path.join(HERE, "fixtures", name)) as f:
        return json.load(f)


# -- the Executor's cell: phases, the compiler's copy, spans, starvation -------

@pytest.fixture(scope="module")
def executor():
    return inside.reduce_inside(_fixture("inside_executor.json"), steps=2)


def test_phase_of_reads_the_op_name_path():
    p = inside.phase_of
    assert p("all-reduce.3", "all-reduce", "jit(step)/hetu_opt/x") \
        == "collective"
    assert p("fused_sgd.5", "mosaic",
             "jit(step_fn)/hetu_opt/Optimizer_SGDOptimizer_37/fused_sgd/"
             "pallas_call:") == "opt"
    assert p("flash_fwd.17", "mosaic",
             "jit(step)/transpose(jvp(hetu_fwd))/while/body/closed_call/"
             "checkpoint/rematted_computation/flash_fwd/pallas_call:") \
        == "recompute"
    assert p("flash_bwd_dq.9", "mosaic",
             "jit(step)/transpose(jvp(hetu_fwd))/while/body/closed_call/"
             "checkpoint/flash_bwd_dq/pallas_call:") == "bwd"
    assert p("flash_fwd.18", "mosaic",
             "jit(step)/jvp(hetu_fwd)/while/body/closed_call/flash_fwd/"
             "pallas_call:") == "fwd"
    assert p("reshape.632", "reshape", "jit(step)/reshape:") == "fwd"
    assert p("copy.26", "copy", "") is None


def test_phases_of_the_executor_step(executor):
    ms = executor["phase_ms_per_step"]
    # the gather and one matmul fusion, twice each, over 2 steps
    assert ms["fwd"] == pytest.approx(
        (12620 + 12612 + 2 * 2418) / 1e6 / 2)
    # the zero fill of the table-sized gradient and the scatter into it
    assert ms["bwd"] == pytest.approx(
        (3267583 + 3269726 + 245035 + 244935) / 1e6 / 2)
    # both fused_sgd calls AND the table copy the compiler put before the
    # kernel: `%copy.26` has no op_name; of its two consumers (`%fusion`,
    # the forward gather, 12.6 us; `%fused_sgd.5`, 9.6 ms) the longer wins
    assert ms["opt"] == pytest.approx(
        (9588163 + 9585096 + 2 * 10 + 6520872 + 6522003) / 1e6 / 2)
    assert ms["recompute"] == 0.0 and ms["collective"] == 0.0
    # `%copy-start.5`: no path, and its consumer is not in the fixture
    assert ms["unattributed"] == pytest.approx(3 * 6 / 1e6 / 2)
    whole = 30068 + 7027279 + 32216154 + 18
    assert executor["device_self_ms_per_step"] == pytest.approx(
        whole / 1e6 / 2)
    assert executor["attributed_pct_min"] == pytest.approx(
        100.0 * (1 - 18 / whole))
    assert executor["scoped"] is True


def test_kernel_table_of_the_executor_step(executor):
    (k,) = executor["kernels"]
    assert (k["kernel"], k["phase"]) == ("fused_sgd", "opt")
    assert k["calls_per_step"] == 2.0          # the table's and one dense
    assert k["ms_per_step"] == pytest.approx(
        (9588163 + 9585096 + 20) / 1e6 / 2)
    assert executor["flash"] is None


def test_span_medians(executor):
    h = executor["host"]
    assert h["steps"] == 3 and h["step_nums"] == [6, 7, 8]
    assert h["children_in_order"] is True and h["compiled"] == 0
    assert h["step_ms_p50"] == pytest.approx(1.49949)
    assert executor["outside_ms_p50"] == pytest.approx(1.53749)
    # feed + dl_wait a step: 67530, 24180, 25200 ns
    assert h["input_ms_p50"] == pytest.approx(0.0252)
    assert h["dispatch_ms_p50"] == pytest.approx(1.38313)
    # prefetch + poststep a step: 30790, 26330, 40190 ns
    assert h["poststep_ms_p50"] == pytest.approx(0.03079)
    assert h["span_ms_p50"]["hetu.build"] == pytest.approx(0.01951)
    assert h["ps_blocked_ms_per_step"] == pytest.approx(
        (4650 + 3840 + 3740 + 4520 + 3570 + 4690) / 3 / 1e6)
    # the first step's children: 1816810 ns of its 1947330
    assert h["coverage_pct_min"] == pytest.approx(100 * 1816810 / 1947330)


def test_starved_share(executor):
    """The window runs from the first hetu_step's start (0) to the last
    device op's end (41049190). The device starts at 1731784, inside the
    first hetu_step, pauses 14 ns, then runs through the other two."""
    assert executor["window_ms"] == pytest.approx(41.04919)
    assert executor["starved_pct"] == pytest.approx(
        100.0 * (1731784 + 14) / 41049190)


def test_a_program_without_the_names_reads_as_nothing():
    raw = _fixture("inside_executor.json")
    bare = {"chips": [{"chip": 0, "modules": ["jit_step_fn(1)"], "ops": [
        [o[0].replace("fused_sgd", "Optimizer_SGDOptimizer"), o[1], o[2],
         o[3].replace("hetu_opt/", "").replace("/fused_sgd", "")]
        for o in raw["chips"][0]["ops"]]}],
        "host": [e for e in raw["host"] if e[0] == "run_call"]}
    r = inside.reduce_inside(bare)
    assert r["scoped"] is False and r["host"] is None
    assert r["starved_pct"] is None and r["flash"] is None
    assert r["outside_ms_p50"] == pytest.approx(1.53749)
    run = {"trace": {"steps": 2}, "counters": {}, "device": {"kind": "x"}}
    import unittest.mock as mock
    with mock.patch.object(inside, "for_run", return_value=r):
        for reader in ("fwd_ms_per_step", "opt_ms_per_step",
                       "recompute_ms_per_step", "flash_attn_time_pct",
                       "flash_attn_roofline_pct", "exec_input_ms_p50",
                       "exec_starved_pct", "ps_blocked_ms_per_step"):
            mod = manifest.load_py(os.path.join(
                ROOT, "benchmark", "layer_metrics", reader + ".py"), reader)
            assert mod.read(run) is None, reader
    # an end-to-end run, and a traced run whose trace is gone: no raise
    assert inside.for_run({"trace": None}) is None
    cell = type("Cell", (), {"bench_dir": "/nonexistent", "name": "c"})()
    assert inside.for_run({"trace": {"steps": 1}, "cell": cell}) is None


def test_readers_over_the_executor_fixture(executor):
    import unittest.mock as mock
    run = {"trace": {"steps": 2}, "counters": {"ps": {"sync_pulls": 0}},
           "device": {"kind": "TPU v5 lite"}}
    want = {"fwd_ms_per_step": executor["phase_ms_per_step"]["fwd"],
            "bwd_ms_per_step": executor["phase_ms_per_step"]["bwd"],
            "opt_ms_per_step": executor["phase_ms_per_step"]["opt"],
            "recompute_ms_per_step": None,
            "exec_input_ms_p50": 0.0252, "exec_dispatch_ms_p50": 1.38313,
            "exec_poststep_ms_p50": 0.03079,
            "exec_starved_pct": executor["starved_pct"],
            "ps_blocked_ms_per_step": 25010 / 3 / 1e6,
            "flash_attn_time_pct": None}
    with mock.patch.object(inside, "for_run", return_value=executor):
        for reader, value in want.items():
            mod = manifest.load_py(os.path.join(
                ROOT, "benchmark", "layer_metrics", reader + ".py"), reader)
            got = mod.read(run)
            assert got == (pytest.approx(value) if value is not None
                           else None), reader


def test_kernel_flops():
    # bert-base at 128 x 512 on one chip: B*H = 1536, T = 512, d = 64
    assert kernel_flops.flash_fwd_flops(1536, 512, 64) == 4 * 1536 * 512 \
        * 512 * 64
    assert kernel_flops.flash_bwd_flops(1536, 512, 64) == 2.5 \
        * kernel_flops.flash_fwd_flops(1536, 512, 64)
    assert peaks.peaks("TPU v5 lite")["tflops"] == 197.0


# -- two chips of the dp4 cell: phases, kernels, flash share and roofline -------

@pytest.fixture(scope="module")
def two_chips():
    return inside.reduce_inside(_fixture("inside_two_chips.json"))


# ns by phase, from the fixture's lines: chip 0, chip 1
FWD = (921775 + 728082 + 10204548, 921695 + 726447 + 10204808)
RECOMPUTE = (752880 + 10204581, 752958 + 10204730)
BWD = (2231299 + 8438982 + 11093550, 2231403 + 8438852 + 11096488)
COLLECTIVE = (501286 + 1613038, 506187 + 1612513)
# `%copy-done.59` has no op_name; `%multiply_subtract_fusion.8` (hetu_opt)
# consumes it
OPT = (64050 + 1525 + 1344486, 64254 + 1786 + 1345471)
UNATTRIBUTED = (260492, 260930)         # `%convert.112`: nothing consumes it
WHOLE = tuple(sum(t) for t in zip(FWD, RECOMPUTE, BWD, COLLECTIVE, OPT,
                                  UNATTRIBUTED))
FLASH = (10204548 + 10204581 + 8438982 + 11093550,
         10204808 + 10204730 + 8438852 + 11096488)


def test_phases_mean_over_chips(two_chips):
    assert two_chips["chips"] == 2 and two_chips["steps"] == 1
    ms = two_chips["phase_ms_per_step"]
    for phase, ns in (("fwd", FWD), ("recompute", RECOMPUTE), ("bwd", BWD),
                      ("collective", COLLECTIVE), ("opt", OPT),
                      ("unattributed", UNATTRIBUTED)):
        assert ms[phase] == pytest.approx(sum(ns) / 2 / 1e6), phase
    # a recomputed forward is told from the first by its path alone: the
    # same kernel, `rematted_computation` under a `transpose(`
    assert two_chips["device_self_ms_per_step"] == pytest.approx(
        sum(WHOLE) / 2 / 1e6)
    assert two_chips["attributed_pct_min"] == pytest.approx(
        100.0 * (1 - 260930 / WHOLE[1]))
    assert two_chips["host"] is None and two_chips["starved_pct"] is None


def test_kernel_shares_by_name_and_phase(two_chips):
    rows = {(k["kernel"], k["phase"]): k for k in two_chips["kernels"]}
    assert set(rows) == {("flash_fwd", "fwd"), ("flash_fwd", "recompute"),
                         ("flash_bwd_dq", "bwd"), ("flash_bwd_dkv", "bwd")}
    assert all(k["calls_per_step"] == 1.0 for k in rows.values())
    assert rows["flash_fwd", "recompute"]["ms_per_call"] == pytest.approx(
        (10204581 + 10204730) / 2 / 1e6)
    assert rows["flash_bwd_dkv", "bwd"]["time_pct"] == pytest.approx(
        100.0 * (11093550 + 11096488) / sum(WHOLE))
    assert [k["kernel"] for k in two_chips["kernels"]][0] == "flash_bwd_dkv"


def test_flash_share_and_roofline(two_chips):
    f = two_chips["flash"]
    assert f["time_pct"] == pytest.approx(100.0 * sum(FLASH) / sum(WHOLE))
    assert f["seconds"] == pytest.approx(sum(FLASH) / 1e9)
    # a chip: two forwards (one recomputed) and one backward, which its two
    # kernels share, at B*H = 128 * 12, T = 512, d = 64
    a_chip = (2 * kernel_flops.flash_fwd_flops(1536, 512, 64)
              + kernel_flops.flash_bwd_flops(1536, 512, 64))
    assert f["flops"] == pytest.approx(2 * a_chip)
    import unittest.mock as mock
    run = {"trace": {"steps": 1}, "device": {"kind": "TPU v5 lite"},
           "counters": {}}
    with mock.patch.object(inside, "for_run", return_value=two_chips):
        read = lambda name: manifest.load_py(os.path.join(       # noqa: E731
            ROOT, "benchmark", "layer_metrics", name + ".py"), name).read(run)
        assert read("flash_attn_time_pct") == pytest.approx(f["time_pct"])
        assert read("flash_attn_roofline_pct") == pytest.approx(
            100.0 * 2 * a_chip / (sum(FLASH) / 1e9) / 197e12)
        assert read("recompute_ms_per_step") == pytest.approx(
            sum(RECOMPUTE) / 2 / 1e6)
        assert read("exec_dispatch_ms_p50") is None    # no Executor here


def test_render_prints_the_remainder(two_chips, executor):
    text = inside.render(two_chips, tflops=197.0)
    assert "unattributed" in text and "flash attention:" in text
    assert "% of 197 TFLOP/s" in text
    text = inside.render(executor)
    assert "hetu.dispatch" in text and "starved:" in text


def test_op_names_reads_the_metadata_stat_from_the_wire(tmp_path):
    """`tf_op` sits on the XEventMetadata, which ProfileData does not show:
    a hand-assembled XSpace (xplane.proto field numbers) round-trips."""
    def varint(n):
        out = b""
        while True:
            b, n = n & 0x7F, n >> 7
            out += bytes([b | (0x80 if n else 0)])
            if not n:
                return out

    def field(num, payload):
        if isinstance(payload, int):
            return varint(num << 3) + varint(payload)
        return varint(num << 3 | 2) + varint(len(payload)) + payload

    def entry(key, value):
        return field(1, key) + field(2, value)

    stat_meta = field(1, 7) + field(2, b"tf_op")
    ev1 = (field(1, 1) + field(2, b"%flash_fwd.3 = bf16[8,8,8] custom-call()")
           + field(5, field(1, 7) + field(5, b"jit(step)/jvp(hetu_fwd)/f")))
    ev2 = field(1, 2) + field(2, b"%copy.1 = f32[2] copy()")
    plane = (field(2, b"/device:TPU:0") + field(4, entry(1, ev1))
             + field(4, entry(2, ev2)) + field(5, entry(7, stat_meta))
             + field(3, field(2, b"XLA Ops")))
    host = field(2, b"/host:CPU") + field(4, entry(1, ev1))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(field(1, plane) + field(1, host))
    assert inside.op_names(str(path)) == {"/device:TPU:0": {
        "%flash_fwd.3 = bf16[8,8,8] custom-call()":
            "jit(step)/jvp(hetu_fwd)/f"}}


# -- the PS legs, through the adapter's hybrid path on the CPU ------------------

def test_ps_blocked_reader_through_the_hybrid_adapter(tmp_path, monkeypatch,
                                                      capsys):
    """A traced run of wdl-criteo under hybrid-ps-bs128 (2 PS servers on
    the host, tiny table): the reader finds the run's own capture, reads
    the `hetu.ps_pull` / `hetu.ps_push` spans the program wrote into it
    and reports the blocked time a step. The CPU capture has no TPU plane,
    so the harness's own reducer is given the recorded one."""
    from test_benchmark import _add, _shrink
    from benchmark.harness import device, main
    from benchmark.reduce import trace
    import shutil
    root = tmp_path
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    monkeypatch.setattr(device, "PLATFORM", "cpu")
    recorded = _fixture("trace_two_chips.json")
    monkeypatch.setattr(trace, "read_xplane", lambda path: recorded)
    _shrink(root, "wdl-criteo", "hybrid-ps-bs128", {"table_rows": 20000},
            {"batches": 8, "trace_steps": 6, "warmup_steps": 3})
    entry = {"unit": "ms", "better": "lower", "source": "program_span",
             "layer": "PS tier", "moves": "samples_per_s",
             "workloads": ["wdl-criteo.hybrid"]}
    _add(root, cell={"name": "wdl-criteo.hybrid", "config": "wdl-criteo",
                     "traffic": "hybrid-ps-bs128", "chips": 1,
                     "why": "test"},
         metrics=[({"name": "ps_blocked_ms_per_step.samples", **entry}, None),
                  ({"name": "exec_dispatch_ms_p50.hybrid", **entry}, None),
                  ({"name": "opt_ms_per_step.hybrid", **entry}, None)])
    rc = main.main(["--workload", "wdl-criteo.hybrid", "--seed", "1",
                    "--seconds", "0.3", "--trace", "1"], root=str(root),
                   t0=0.0)
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"], line["check"]
    m = line["metrics"]
    # six traced steps, each blocked on a pull and a push for some time
    assert 0.0 < m["ps_blocked_ms_per_step.samples"]["value"] < 1000.0
    assert m["exec_dispatch_ms_p50.hybrid"]["value"] > 0.0
    # no device plane in a CPU capture: the phase reader leaves its metric out
    assert "opt_ms_per_step.hybrid" not in m
    r = inside.reduce_inside(inside.read_inside(trace.newest_xplane(str(
        root / "benchmark" / ".cache" / "trace" / "wdl-criteo.hybrid"))))
    assert r["host"]["steps"] == 6 and r["host"]["children_in_order"]
    assert r["chips"] == 0 and r["starved_pct"] is None
