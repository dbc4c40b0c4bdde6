"""CPU tests of what ISSUE 25 added to the benchmark: the olmoe-1b-7b
adapter at a toy size against its reference, the zipf generator, the FLOPs
functions against hand counts at the published sizes, and reduce/moe.py
with its readers on a fixture cut from a TPU v5e trace of the cell. No
number here is a device number."""
import json
import os
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import main, manifest          # noqa: E402
from benchmark.reduce import moe                       # noqa: E402
from benchmark.tests.test_benchmark import (           # noqa: E402,F401
    _last_line, _shrink, on_cpu, root)

CELL = "olmoe-1b-7b.pretrain-seq4096"


def _fixture(name):
    with open(os.path.join(HERE, "fixtures", name)) as f:
        return json.load(f)


def test_olmoe_adapter_runs_and_agrees_with_reference(root, on_cpu, capsys):
    _shrink(root, "olmoe-1b-7b", "pretrain-seq4096",
            {"hidden_size": 64, "intermediate_size": 32,
             "num_attention_heads": 4, "num_key_value_heads": 4,
             "num_experts": 8, "num_experts_per_tok": 2,
             "num_hidden_layers": 2, "vocab_size": 512,
             "max_position_embeddings": 32},
            {"sequences": 4, "seq_len": 32, "sync_every": 2})
    rc = main.main(["--workload", CELL, "--seed", str(2 ** 31 + 11),
                    "--seconds", "0.5", "--trace", "0"],
                   root=str(root), t0=0.0)
    assert rc == 0
    line = _last_line(capsys)
    check = line["check"]
    assert line["correct"], check
    assert check["dropped_picks"] == 0
    assert check["hidden_rel_rms_err"] < 2.5e-2, check
    assert set(check["grad_rel_rms_err"]) == {
        "router", "q_norm", "k_norm", "ln1_scale", "ln2_scale", "lnf_scale"}
    assert line["window"]["compiles"] == 0
    assert line["failed"] == 0
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}


def test_cell_reports_the_new_per_layer_metrics():
    cell = manifest.resolve(ROOT, CELL)
    assert cell.chips == 1 and cell.traffic["generator"] == "lm_zipf"
    names = {m["name"] for m in cell.per_layer}
    assert {"moe_time_pct.tokens", "moe_experts_ms_per_step.tokens",
            "moe_route_dispatch_combine_ms_per_step.tokens",
            "moe_experts_roofline_pct.tokens", "moe_load_max_over_mean",
            "mfu_pct", "flash_attn_time_pct.tokens", "fwd_ms_per_step.tokens",
            "recompute_ms_per_step.tokens", "bwd_ms_per_step.tokens",
            "opt_ms_per_step.tokens"} <= names
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "setup_s"}
    for m in cell.per_layer:
        assert callable(manifest.reader(cell, m["name"]).read)
    # the catalog's keys, the one cut, and nothing else changed
    assert cell.config["num_hidden_layers"] == 1
    assert list(cell.config["reduced"]) == ["num_hidden_layers"]
    assert (cell.config["hidden_size"], cell.config["intermediate_size"],
            cell.config["num_experts"], cell.config["num_experts_per_tok"],
            cell.config["vocab_size"]) == (2048, 1024, 64, 8, 50304)


def test_lm_zipf_is_seeded_skewed_and_shifted():
    cell = manifest.resolve(ROOT, CELL)
    gen = manifest.generator(cell)
    traffic = dict(cell.traffic, sequences=4, seq_len=512, batches=2)
    big = 2 ** 31 + 12345
    a = gen.generate(traffic, cell.config, big)
    b = gen.generate(traffic, cell.config, big)
    c = gen.generate(traffic, cell.config, big + 1)
    assert len(a) == 2 and a[0]["tokens"].shape == (4, 512)
    assert a[0]["tokens"].dtype == np.int32
    for x, y in zip(a, b):
        assert np.array_equal(x["tokens"], y["tokens"])
        assert np.array_equal(x["targets"], y["targets"])
    assert not np.array_equal(a[0]["tokens"], c[0]["tokens"])
    # the target of a position is the next token
    assert np.array_equal(a[0]["tokens"][:, 1:], a[0]["targets"][:, :-1])
    ids = np.concatenate([x["tokens"].ravel() for x in a])
    assert ids.min() >= 0 and ids.max() < cell.config["vocab_size"]
    # zipf at exponent 1.1 over 50,304 ids: the commonest id takes ~ 9 % of
    # the draws (1 / sum r^-1.1), a uniform draw 0.002 %
    top = np.bincount(ids).max() / ids.size
    assert 0.05 < top < 0.15, top
    # another seed, another rank -> id map
    assert np.bincount(ids).argmax() != np.bincount(
        np.concatenate([x["tokens"].ravel() for x in c])).argmax()
    one = gen.generate(traffic, cell.config, big, sequences=1)
    assert len(one) == 1 and one[0]["tokens"].shape == (1, 512)


def test_flops_against_hand_counts_at_the_published_sizes():
    D, F, E, k, V, T = 2048, 1024, 64, 8, 50304, 4096
    # experts: 8 picks x 3 projections x 2*2048*1024 = 100.66 MFLOP a token
    experts = k * 3 * 2 * D * F
    assert experts == 100_663_296
    router = 2 * D * E                                   # 0.26 MFLOP
    assert moe.moe_train_flops_per_token(D, F, E, k) == 3 * (experts + router)
    attention = 8 * D * D + 2 * T * D                    # 50.33 MFLOP
    head = 2 * D * V                                     # 206.05 MFLOP
    forward = attention + experts + router + head
    assert round(forward / 1e6, 1) == 357.3
    assert moe.olmoe_train_flops_per_token(D, 1, F, E, k, V, T) == 3 * forward
    # the shares ISSUE 25 states: head 58 %, MoE block 28 % at one layer
    assert round(100 * head / forward) == 58
    assert round(100 * (experts + router) / forward) == 28
    # 16 layers: 8 % and 61 %
    full = 16 * (attention + experts + router) + head
    assert round(100 * head / full) == 8
    assert round(100 * 16 * (experts + router) / full) == 61
    # a step of 8 x 4096 tokens requires 35.1 TFLOP
    assert round(3 * forward * 8 * T / 1e12, 1) == 35.1
    picks = 8 * T * k
    assert moe.moe_expert_matmul_flops(picks, D, F) == 2 * picks * D * F
    assert moe.moe_expert_matmul_bytes(picks, D, F, E) == 2 * (
        picks * D + E * D * F + picks * F)
    # compute-bound on a chip of 197 TFLOP/s and 819 GB/s (240 FLOP a byte)
    assert moe.moe_expert_matmul_flops(picks, D, F) \
        / moe.moe_expert_matmul_bytes(picks, D, F, E) > 500


def test_scope_of_reads_the_op_name_path():
    path = ("jit(<lambda>)/transpose(jvp(hetu_fwd))/while/body/closed_call/"
            "checkpoint/rematted_computation/hetu_moe_experts/"
            "ragged_dot_general:")
    assert moe.scope_of(path) == "hetu_moe_experts"
    assert moe.scope_of("jit(f)/jvp(hetu_fwd)/while/body/closed_call/"
                        "hetu_moe_route/top_k:") == "hetu_moe_route"
    assert moe.scope_of("jit(f)/jvp(hetu_fwd)/flash_fwd/pallas_call:") is None
    assert moe.scope_of("") is None


def test_a_program_without_the_scopes_reads_as_nothing():
    """The parent of ISSUE 25, or any dense model: no table, no metric, no
    exception."""
    raw = _fixture("inside_two_chips.json")
    assert moe.reduce_moe(raw, steps=1) is None
    assert "no hetu_moe" in moe.render(None)
    run = {"trace": None, "counters": {}}
    cell = manifest.resolve(ROOT, CELL)
    for name in ("moe_time_pct", "moe_experts_ms_per_step",
                 "moe_route_dispatch_combine_ms_per_step",
                 "moe_experts_roofline_pct", "moe_load_max_over_mean"):
        assert manifest.reader(cell, name).read(run) is None


@pytest.fixture(scope="module")
def moe_table():
    return moe.reduce_moe(_fixture("moe_one_chip.json"), steps=1)


def test_moe_table_from_the_fixture(moe_table):
    """Every expected number is worked out here from the fixture's lines."""
    raw = _fixture("moe_one_chip.json")
    ops = raw["chips"][0]["ops"]
    # the compiler's grouped matmuls lost the program's path: found by name
    gmm = [op for op in ops if op[0].startswith("%ragged-dot-none")]
    assert len(gmm) == 12
    assert all("tpu_custom_call" in op[0] and op[3] == "ragged-dot-none:"
               for op in gmm)
    by_scope = {"hetu_moe_experts": sum(op[2] for op in gmm)}
    for text, _start, dur, op_name in ops:
        scope = moe.scope_of(op_name)
        if scope:
            by_scope[scope] = by_scope.get(scope, 0.0) + dur
    # the fixture's ops do not nest: self time is duration
    for scope in moe.SCOPES:
        assert sum(moe_table["scope_ms_per_step"][scope].values()) \
            == pytest.approx(by_scope[scope] / 1e6), scope
    whole = sum(op[2] for op in ops)
    assert moe_table["device_self_ms_per_step"] == pytest.approx(whole / 1e6)
    assert moe_table["time_pct"] == pytest.approx(
        100.0 * sum(by_scope.values()) / whole)
    g = moe_table["grouped_matmul"]
    assert g["calls_per_step"] == 12
    assert g["ms_per_step"] == pytest.approx(sum(op[2] for op in gmm) / 1e6)
    assert 8.5 < g["ms_per_call"] < 10.0        # as on the chip, PERF.md
    # a grouped matmul takes the phase of what reads its result: each
    # phase holds at least the three calls' worth it must
    experts = moe_table["scope_ms_per_step"][moe.EXPERTS]
    assert all(experts[p] > 3 * 8.5 for p in moe.PHASES), experts
    assert "grouped matmuls: 12.0 calls" in moe.render(moe_table)
    # the scope-less metadata call (tile offsets) is not a grouped matmul
    assert not moe.is_grouped_matmul(
        {"kind": "mosaic", "op_name": "ragged-dot-metadata:",
         "name": "ragged-dot-metadata.2"})
    assert moe.is_grouped_matmul(
        {"kind": "mosaic", "name": "gmm_fwd.3", "op_name":
         "jit(f)/jvp(hetu_fwd)/hetu_moe_experts/gmm_fwd/pallas_call:"})


def test_readers_over_the_fixture(moe_table, monkeypatch):
    cell = manifest.resolve(ROOT, CELL)
    run = {"trace": {"steps": 1}, "cell": cell,
           "device": {"kind": "TPU v5 lite"},
           "counters": {"moe": {"max_over_mean": [1.75]}}}
    monkeypatch.setattr(moe, "for_run", lambda _run: moe_table)
    read = lambda name: manifest.reader(cell, name).read(run)
    scopes = moe_table["scope_ms_per_step"]
    assert read("moe_time_pct") == moe_table["time_pct"]
    assert read("moe_experts_ms_per_step") == pytest.approx(
        sum(scopes["hetu_moe_experts"].values()))
    assert read("moe_route_dispatch_combine_ms_per_step") == pytest.approx(
        sum(sum(scopes[s].values()) for s in moe.SCOPES
            if s != "hetu_moe_experts"))
    g = moe_table["grouped_matmul"]
    flops = 12 * 2 * (8 * 4096 * 8) * 2048 * 1024
    assert read("moe_experts_roofline_pct") == pytest.approx(
        100.0 * flops / (g["ms_per_step"] / 1e3) / 197e12)
    assert read("moe_load_max_over_mean") == 1.75
