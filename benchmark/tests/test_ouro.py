"""CPU tests of what ISSUE 29 added to the benchmark: the ouro-2.6b adapter
at a toy size against its reference, the looped decoder's FLOPs function
against hand counts at the published sizes, and reduce/loop.py with its
readers on a fixture cut from a TPU v5e trace of the cell. No number here is
a device number."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import main, manifest          # noqa: E402
from benchmark.reduce import loop                      # noqa: E402
from benchmark.tests.test_benchmark import (           # noqa: E402,F401
    _last_line, _shrink, on_cpu, root)

OURO_CELL = "ouro-2.6b.pretrain-seq4096-b1"


def _loop_fixture(name):
    with open(os.path.join(HERE, "fixtures", name)) as f:
        return json.load(f)


def test_ouro_adapter_runs_and_agrees_with_reference(root, on_cpu, capsys):
    _shrink(root, "ouro-2.6b", "pretrain-seq4096-b1",
            {"hidden_size": 64, "intermediate_size": 96, "head_dim": 16,
             "num_attention_heads": 4, "num_key_value_heads": 4,
             "num_hidden_layers": 2, "total_ut_steps": 3, "vocab_size": 512,
             "max_position_embeddings": 32},
            {"sequences": 2, "seq_len": 32, "sync_every": 2})
    rc = main.main(["--workload", OURO_CELL, "--seed", str(2 ** 31 + 29),
                    "--seconds", "0.5", "--trace", "0"],
                   root=str(root), t0=0.0)
    assert rc == 0
    line = _last_line(capsys)
    check = line["check"]
    assert line["correct"], check
    assert len(check["nll_abs_err"]) == len(check["exit_rel_rms_err"]) == 3
    assert max(check["exit_rel_rms_err"]) < 2.5e-2, check
    assert check["q_sum_abs_err"] < 1e-5
    assert set(check["grad_rel_rms_err"]) == {
        "exit_gate_w", "exit_gate_b", "lnf_scale", "ln1_scale",
        "ln1_post_scale", "ln2_scale", "ln2_post_scale", "wqkv_layer0"}
    assert 1.0 <= check["expected_exit_step"] <= 3.0
    assert abs(sum(check["q_mean"]) - 1.0) < 1e-5
    assert line["window"]["compiles"] == 0
    assert line["failed"] == 0
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}


def test_ouro_cell_reports_the_new_per_layer_metrics():
    cell = manifest.resolve(ROOT, OURO_CELL)
    assert cell.chips == 1 and cell.traffic["generator"] == "lm_zipf"
    assert (cell.traffic["sequences"], cell.traffic["seq_len"]) == (1, 4096)
    names = {m["name"] for m in cell.per_layer}
    assert names == {
        "loop_exit_ms_per_step.tokens", "loop_exit_time_pct.tokens",
        "loop_expected_exit_step", "compiles_in_window.tokens",
        "device_idle_pct.tokens", "peak_hbm_gib.tokens", "mfu_pct",
        "fwd_ms_per_step.tokens", "recompute_ms_per_step.tokens",
        "bwd_ms_per_step.tokens", "opt_ms_per_step.tokens",
        "flash_attn_time_pct.tokens", "mosaic_time_pct.tokens"}
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "setup_s"}
    for m in cell.per_layer:
        assert callable(manifest.reader(cell, m["name"]).read)
    # the published config.json's keys, the one cut, and nothing else changed
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
        "max_position_embeddings": 65536, "max_window_layers": 48,
        "model_type": "ouro", "num_attention_heads": 16,
        "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "total_ut_steps": 4,
        "early_exit_threshold": 1, "use_sliding_window": False,
        "vocab_size": 49152}
    for key, value in published.items():
        assert cell.config[key] == value, key
    assert cell.config["num_hidden_layers"] == 6
    assert list(cell.config["reduced"]) == ["num_hidden_layers"]
    assert (cell.config["hidden_size"], cell.config["intermediate_size"],
            cell.config["num_attention_heads"], cell.config["head_dim"],
            cell.config["total_ut_steps"], cell.config["vocab_size"]) == (
                2048, 5632, 16, 128, 4, 49152)
    assert cell.config["assumed"]["exit_entropy_weight"] == 0.05


def test_looped_flops_against_hand_counts_at_the_published_sizes():
    D, F, V, T, L, N = 2048, 5632, 49152, 4096, 6, 4
    matmuls = 4 * 2 * D * D + 3 * 2 * D * F      # 102.76 MFLOP an application
    assert matmuls == 2 * 51_380_224
    attention = 2 * T * D                         # causal: half of 4*T*D
    head = 2 * D * V                              # 201.33 MFLOP a pass
    forward = N * (L * (matmuls + attention) + head)
    assert loop.looped_train_flops_per_token(D, L, F, V, T, N) == 3 * forward
    # ISSUE 29's sizing: 7.40 GFLOP of trunk matmuls, 1.21 of attention,
    # 2.42 of head, 11.0 a token
    assert round(3 * N * L * matmuls / 1e9, 2) == 7.40
    assert round(3 * N * L * attention / 1e9, 2) == 1.21
    assert round(3 * N * head / 1e9, 2) == 2.42
    assert round(3 * forward / 1e9, 1) == 11.0
    # the head is 22 % of the step here and 3.4 % at the published 48 layers
    assert round(100 * N * head / forward) == 22
    full = N * (48 * (matmuls + attention) + head)
    assert round(100 * N * head / full, 1) == 3.4
    # one loop of one layer is the plain decoder's count
    assert loop.looped_train_flops_per_token(D, 1, F, V, T, 1) == 3 * (
        matmuls + attention + head)


def test_in_scope_reads_the_op_name_path():
    assert loop.in_scope("jit(<lambda>)/jvp(hetu_fwd)/hetu_exit/"
                         "fused_ce_fwd/pallas_call")
    assert loop.in_scope("jit(<lambda>)/transpose(jvp(hetu_fwd))/hetu_exit/"
                         "n...d,d->n.../dot_general")
    assert not loop.in_scope("jit(f)/jvp(hetu_fwd)/while/body/while/body/"
                             "closed_call/flash_fwd/pallas_call:")
    assert not loop.in_scope("")


def test_a_program_without_the_exit_scope_reads_as_nothing():
    """The parent of ISSUE 29, or any model with one exit: no table, no
    metric, no exception."""
    for fixture in ("inside_two_chips.json", "moe_one_chip.json"):
        assert loop.reduce_loop(_loop_fixture(fixture), steps=1) is None
    assert "no hetu_exit" in loop.render(None)
    run = {"trace": None, "counters": {}}
    cell = manifest.resolve(ROOT, OURO_CELL)
    for name in ("loop_exit_ms_per_step", "loop_exit_time_pct",
                 "loop_expected_exit_step"):
        assert manifest.reader(cell, name).read(run) is None


@pytest.fixture(scope="module")
def loop_table():
    return loop.reduce_loop(_loop_fixture("loop_one_chip.json"), steps=1)


def test_loop_table_from_the_fixture(loop_table):
    """Every expected number is worked out here from the fixture's lines."""
    ops = _loop_fixture("loop_one_chip.json")["chips"][0]["ops"]
    under = [op for op in ops if loop.in_scope(op[3])]
    assert under and len(under) < len(ops)
    # the three fused cross-entropy kernels keep the program's path
    kernels = {name: [op for op in under if op[0].startswith("%" + name)]
               for name in ("fused_ce_fwd", "fused_ce_bwd_dh",
                            "fused_ce_bwd_dw")}
    assert all(kernels.values()), {k: len(v) for k, v in kernels.items()}
    # the fixture's ops do not nest: self time is duration
    whole = sum(op[2] for op in ops)
    exit_ns = sum(op[2] for op in under)
    assert loop_table["device_self_ms_per_step"] == pytest.approx(whole / 1e6)
    assert loop_table["exit_total_ms_per_step"] == pytest.approx(
        exit_ns / 1e6)
    assert loop_table["time_pct"] == pytest.approx(100.0 * exit_ns / whole)
    by = loop_table["exit_ms_per_step"]
    assert by["fwd"] == pytest.approx(sum(
        op[2] for op in under if "transpose(" not in op[3]) / 1e6)
    assert by["bwd"] == pytest.approx(sum(
        op[2] for op in under if "transpose(" in op[3]) / 1e6)
    assert by["recompute"] == 0.0       # the head is outside the checkpoint
    rows = loop_table["instructions"]
    assert rows == sorted(rows, key=lambda r: -r["ms_per_step"])
    # the head's kernels are the longest instructions under the scope
    assert {r["family"] for r in rows[:3]} == {
        "mosaic:fused_ce_fwd", "mosaic:fused_ce_bwd_dh",
        "mosaic:fused_ce_bwd_dw"}
    assert "fused_ce_bwd_dw" in loop.render(loop_table)


def test_loop_readers_over_the_fixture(loop_table, monkeypatch):
    cell = manifest.resolve(ROOT, OURO_CELL)
    run = {"trace": {"steps": 1}, "cell": cell,
           "device": {"kind": "TPU v5 lite"},
           "counters": {"loop": {"q_mean": [0.4, 0.3, 0.2, 0.1],
                                 "expected_exit_step": 2.0}}}
    monkeypatch.setattr(loop, "for_run", lambda _run: loop_table)
    read = lambda name: manifest.reader(cell, name).read(run)
    assert read("loop_exit_ms_per_step") == loop_table[
        "exit_total_ms_per_step"]
    assert read("loop_exit_time_pct") == loop_table["time_pct"]
    assert read("loop_expected_exit_step") == 2.0
