"""CPU tests of what ISSUE 31 added to the benchmark: the granite-4.0-h-micro
adapter at a toy size against its reference, the hybrid step's and the
chunked scan's FLOPs and bytes functions against hand counts, and
reduce/ssm.py with its readers on a fixture cut from a TPU v5e trace of the
cell. No number here is a device number."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import main, manifest          # noqa: E402
from benchmark.reduce import ssm                       # noqa: E402
from benchmark.tests.test_benchmark import (           # noqa: E402,F401
    _last_line, _shrink, on_cpu, root)

CELL = "granite-4.0-h-micro.pretrain-seq8192-b1"
SSM_METRICS = {"ssm_time_pct.tokens", "ssm_scan_ms_per_step.tokens",
               "ssm_conv_gate_ms_per_step.tokens",
               "ssm_scan_roofline_pct.tokens"}


def _ssm_fixture():
    with open(os.path.join(HERE, "fixtures", "ssm_one_chip.json")) as f:
        return json.load(f)


def test_granite_adapter_runs_and_agrees_with_reference(root, on_cpu, capsys):
    _shrink(root, "granite-4.0-h-micro", "pretrain-seq8192-b1",
            {"hidden_size": 64, "shared_intermediate_size": 128,
             "num_attention_heads": 4, "num_key_value_heads": 2,
             "attention_multiplier": 0.0625, "mamba_n_heads": 8,
             "mamba_d_head": 16, "mamba_d_state": 16, "mamba_chunk_size": 8,
             "num_hidden_layers": 3,
             "layer_types": ["mamba", "mamba", "attention"],
             "vocab_size": 512, "max_position_embeddings": 32},
            {"sequences": 2, "seq_len": 32, "sync_every": 2})
    rc = main.main(["--workload", CELL, "--seed", str(2 ** 31 + 31),
                    "--seconds", "0.5", "--trace", "0"],
                   root=str(root), t0=0.0)
    assert rc == 0
    line = _last_line(capsys)
    check = line["check"]
    assert line["correct"], check
    assert set(check["hidden_rel_rms_err"]) == {"after_mamba_run",
                                                "after_stack"}
    assert set(check["grad_rel_rms_err"]) == {
        "A_log", "dt_bias", "D", "conv_w", "conv_b", "ssm_norm", "ln1_scale",
        "ln2_scale", "lnf_scale", "w_in_layer0", "w_out_layer0",
        "mlp_in_layer0", "wqkv", "wo"}
    # float32 parts against float64, on the CPU: float32 rounding
    for key in ("own_dt_rel_err", "own_log_decay_rel_rms_err",
                "own_local_state_rel_rms_err",
                "own_entering_state_rel_rms_err"):
        assert check[key] < 1e-5, (key, check[key])
    assert check["grad_tokens"] == 32
    assert line["window"]["compiles"] == 0
    assert line["failed"] == 0
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}


def test_granite_cell_resolves_with_the_new_per_layer_metrics():
    cell = manifest.resolve(ROOT, CELL)
    assert cell.chips == 1 and cell.traffic["generator"] == "lm_zipf"
    assert (cell.traffic["sequences"], cell.traffic["seq_len"]) == (1, 8192)
    names = {m["name"] for m in cell.per_layer}
    assert names == SSM_METRICS | {
        "compiles_in_window.tokens", "device_idle_pct.tokens",
        "peak_hbm_gib.tokens", "mfu_pct", "fwd_ms_per_step.tokens",
        "recompute_ms_per_step.tokens", "bwd_ms_per_step.tokens",
        "opt_ms_per_step.tokens", "flash_attn_time_pct.tokens",
        "mosaic_time_pct.tokens"}
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "setup_s"}
    for m in cell.per_layer:
        assert callable(manifest.reader(cell, m["name"]).read)
    # the catalog row's keys, the one cut, and nothing else changed
    c = cell.config
    assert c["num_hidden_layers"] == 6 and list(c["reduced"]) == [
        "num_hidden_layers"]
    assert "less than one period" in c["reduced"]["num_hidden_layers"].lower()
    published = {
        "attention_bias": False, "attention_multiplier": 0.015625,
        "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 8192,
        "logits_scaling": 8, "mamba_chunk_size": 256,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_n_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 0, "num_key_value_heads": 8,
        "num_local_experts": 0, "position_embedding_type": "nope",
        "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 8192, "tie_word_embeddings": True,
        "vocab_size": 100352}
    assert {k: c[k] for k in published} == published
    kinds = c["layer_types"]
    assert len(kinds) == 40 and [i for i, k in enumerate(kinds)
                                 if k == "attention"] == [5, 15, 25, 35]
    # no other cell reports the mixer's metrics
    other = manifest.resolve(ROOT, "ouro-2.6b.pretrain-seq4096-b1")
    assert not SSM_METRICS & {m["name"] for m in other.per_layer}


def test_required_flops_and_bytes_by_hand():
    """At the cell's shape (1 x 8,192 tokens, 64 heads of 64, state 128, one
    group, chunks of 256), counted by hand."""
    shape = (1, 8192, 64, 64, 128, 256)
    pairs = 256 * 257 // 2                       # causal pairs a chunk
    inside = 32 * pairs * (2 * 128 + 64 * 2 * 64)
    states = 8192 * 64 * 2 * 64 * 128
    assert ssm.ssd_required_flops(*shape) == 3.0 * (inside + 2 * states)
    assert round(ssm.ssd_required_flops(*shape) / 1e9, 1) == 78.2
    # x and y 4096 bf16 channels a position, B and C 128 each, dt 64 floats
    a_position = 2 * (4096 + 4096 + 128 + 128) + 4 * 64
    assert ssm.ssd_required_bytes(*shape) == 3.0 * 8192 * a_position
    # two groups read twice the B and C
    assert (ssm.ssd_required_bytes(*shape, groups=2)
            - ssm.ssd_required_bytes(*shape)) == 3.0 * 8192 * 2 * 256
    # the ridge: at the published peaks the bytes bound this shape
    flops_s = ssm.ssd_required_flops(*shape) / 197e12
    bytes_s = ssm.ssd_required_bytes(*shape) / 819e9
    assert 0.39e-3 < flops_s < bytes_s < 0.52e-3
    assert ssm.scan_roofline_pct(
        100.0, manifest.resolve(ROOT, CELL).config,
        {"sequences": 1, "seq_len": 8192}, "TPU v5 lite") == pytest.approx(
            100.0 * 5 * bytes_s / 0.1)

    c = manifest.resolve(ROOT, CELL).config
    per_token = ssm.granite_train_flops_per_token(c, 8192)
    mlp = 6 * 2048 * 8192
    mamba = (2 * 2048 * (4096 + 4352 + 64) + 2 * 4 * 4352
             + (inside + 2 * states) / 8192 + 2 * 4096 * 2048 + mlp)
    attention = 4 * 2048 * 2048 + 4 * 2048 * 512 + 2 * 8192 * 2048 + mlp
    head = 2 * 2048 * 100352
    assert per_token == pytest.approx(3 * (5 * mamba + attention + head))
    assert round(per_token / 1e9, 2) == 4.03
    # shares of the required work: the mixers' layers, attention's, the head
    assert round(100 * 5 * mamba / (per_token / 3)) == 58
    assert round(100 * attention / (per_token / 3)) == 12
    assert round(100 * head / (per_token / 3)) == 31


# -- reduce/ssm.py on a fixture cut from a v5e trace ---------------------------

def _other_fixture(name):
    with open(os.path.join(HERE, "fixtures", name)) as f:
        return json.load(f)


def test_ssm_table_from_the_fixture():
    """Every expected number is worked out here from the fixture's lines:
    the four scopes are found in forward, recomputed and backward ops."""
    fx = _ssm_fixture()
    ops = fx["chips"][0]["ops"]
    table = ssm.reduce_ssm(fx, steps=1)
    under = [op for op in ops if ssm.scope_of(op[3])]
    assert under and len(under) < len(ops)
    # the fixture's ops do not nest: self time is duration
    assert table["device_self_ms_per_step"] == pytest.approx(
        sum(op[2] for op in ops) / 1e6)
    assert table["ssm_ms_per_step"] == pytest.approx(
        sum(op[2] for op in under) / 1e6)
    assert table["time_pct"] == pytest.approx(
        100 * sum(op[2] for op in under) / sum(op[2] for op in ops))

    def phase(op_name):
        if "transpose(" not in op_name:
            return "fwd"
        return "recompute" if "rematted_computation" in op_name else "bwd"

    for scope in ssm.SCOPES:
        for p in ssm.PHASES:
            want = sum(op[2] for op in under
                       if f"/{scope}/" in op[3] and phase(op[3]) == p) / 1e6
            assert table["scope_ms_per_step"][scope][p] == pytest.approx(
                want), (scope, p)
            # the gate's backward ops are fused into the scan's and the
            # projection's in this program: every other pair has time
            assert want > 0 or (scope, p) == (ssm.GATE, "bwd"), (scope, p)
    rows = table["instructions"]
    assert rows == sorted(rows, key=lambda r: -r["ms_per_step"])
    assert {r["scope"] for r in rows} == set(ssm.SCOPES)
    # the chunked products keep their einsum in the path
    assert any("bcgrls,bcsgrp->bclgrp" in op[3] for op in under)
    text = ssm.render(table)
    assert "hetu_ssm_scan" in text and "recompute" in text


def test_ssm_readers_on_a_traced_run_of_the_fixture(tmp_path, monkeypatch):
    """The four readers through `for_run`, as the harness calls them."""
    cell = manifest.resolve(ROOT, CELL)
    table = ssm.reduce_ssm(_ssm_fixture(), steps=1)
    monkeypatch.setattr(ssm, "for_run", lambda run: table)
    run = {"trace": {"steps": 1}, "cell": cell,
           "device": {"kind": "TPU v5 lite"}}
    by = table["scope_ms_per_step"]
    scan = sum(by[ssm.SCAN].values())
    assert manifest.reader(cell, "ssm_time_pct.tokens").read(
        run) == table["time_pct"]
    assert manifest.reader(cell, "ssm_scan_ms_per_step.tokens").read(
        run) == pytest.approx(scan)
    assert manifest.reader(cell, "ssm_conv_gate_ms_per_step.tokens").read(
        run) == pytest.approx(sum(by[ssm.CONV].values())
                              + sum(by[ssm.GATE].values()))
    # five layers' scans at the bytes bound, 3 x 8192 x 17152 bytes each
    least_ms = 5 * 3 * 8192 * 17152 / 819e9 * 1e3
    assert manifest.reader(cell, "ssm_scan_roofline_pct.tokens").read(
        run) == pytest.approx(100 * least_ms / scan)


@pytest.mark.parametrize("fixture", ["loop_one_chip.json",
                                     "moe_one_chip.json",
                                     "inside_two_chips.json"])
def test_ssm_readers_return_nothing_without_the_scopes(fixture):
    """A program that wrote no `hetu_ssm_*` scope (every other cell; the
    parent of PR 31): no table, and every reader leaves its metric out."""
    assert ssm.reduce_ssm(_other_fixture(fixture), steps=1) is None
    assert "no hetu_ssm_" in ssm.render(None)
    cell = manifest.resolve(ROOT, CELL)
    for run in ({"trace": None, "counters": {}, "cell": cell},
                {"trace": {"steps": 1}, "cell": cell,
                 "device": {"kind": "TPU v5 lite"}}):
        for name in sorted(SSM_METRICS):
            assert manifest.reader(cell, name).read(run) is None, name
