"""CPU tests of what ISSUE 49 added to the benchmark: the laguna-xs.2 adapter
at a toy size against its reference (the three parts of its check), the cell
and its files, the step's FLOPs and the pair counts against hand counts and
closed forms, and reduce/swa.py with its six readers on a fixture cut from a
TPU v5e trace of the cell. No number here is a device number."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import main, manifest          # noqa: E402
from benchmark.reduce import mla, swa                  # noqa: E402
from benchmark.tests.test_benchmark import (           # noqa: E402,F401
    _last_line, _shrink, on_cpu, root)

CELL = "laguna-xs.2.pretrain-seq16384-b1-ep8share"
SWA_METRICS = {"swa_time_pct.tokens", "swa_attn_roofline_pct.tokens",
               "full_attn_roofline_pct.tokens", "swa_computed_pair_pct",
               "attn_rope_ms_per_step.tokens", "attn_gate_ms_per_step.tokens"}
TOY = {"hidden_size": 64, "intermediate_size": 128,
       "moe_intermediate_size": 48, "shared_expert_intermediate_size": 40,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
       "num_attention_heads_per_layer": [4, 6, 6, 6, 4],
       "num_experts": 2, "num_routed_experts": 8, "first_expert_held": 2,
       "num_experts_per_tok": 2, "vocab_size": 512, "sliding_window": 16,
       "max_position_embeddings": 256,
       "rope_parameters": {
           "full_attention": {
               "rope_theta": 10000, "rope_type": "yarn", "factor": 8,
               "original_max_position_embeddings": 16, "beta_slow": 0.01,
               "beta_fast": 1, "attention_factor": 1.2079441541679836,
               "partial_rotary_factor": 0.5},
           "sliding_attention": {"rope_type": "default", "rope_theta": 100,
                                 "partial_rotary_factor": 1}}}


def _fixture(name="swa_one_chip.json"):
    with open(os.path.join(HERE, "fixtures", name)) as f:
        return json.load(f)


def test_laguna_adapter_runs_and_agrees_with_reference(root, on_cpu, capsys):
    """The real structure at a toy size: full attention (4 heads, half a
    head's columns by YaRN's table) + dense, three window layers (6 heads, a
    window of 16 of 64 keys) and a full layer with experts and the shared
    one, 2 of 8 experts held from expert 2 on, top 2; gradients on all 64
    tokens, four windows."""
    _shrink(root, "laguna-xs.2", "pretrain-seq16384-b1-ep8share", TOY,
            {"sequences": 2, "seq_len": 64, "sync_every": 2,
             "warmup_steps": 3})
    adapter = manifest.adapter(manifest.resolve(str(root), CELL))
    rc = main.main(["--workload", CELL, "--seed", str(2 ** 31 + 49),
                    "--seconds", "0.5", "--trace", "0"],
                   root=str(root), t0=0.0)
    assert rc == 0
    line = _last_line(capsys)
    check = line["check"]
    assert line["correct"], check
    # (A) each run of layers held to its own number
    assert list(check["hidden_rel_rms_err"]) == [
        "after_attention+dense_run_0", "after_window_run_1",
        "after_attention_run_2"]
    assert set(check["grad_rel_rms_err"]) == set(adapter.GRAD_TOLS) == {
        "ln1_scale", "ln2_scale", "lnf_scale", "router", "wq", "wk", "wv",
        "wo", "wg", "dense_w1_layer0", "shared_w1", "shared_w2",
        "expert_w1_layer1", "expert_w2_layer1"}
    assert check["bias_entries_unexplained"] == 0
    assert check["dropped_picks"] == 0
    assert 0 < sum(check["held_picks"]) < 4 * 64 * 2
    # (B) every token's picks of four expert layers against float64 scores
    assert check["picks_checked"] == 4 * 64 * 2
    assert check["picks_differ_share"] <= adapter.PICKS_DIFFER_MAX_SHARE
    # (C) float32 parts and kept sets against float64, on the CPU: the
    # rotation's result and the mixer's output round to bfloat16
    assert check["own_out_rel_rms_err_window"] < 1e-2
    assert check["own_out_rel_rms_err_full"] < 1e-2
    assert check["own_window_edge_share"] < 1e-2
    assert check["own_rope_rel_rms_err_window"] < 4e-3
    assert check["own_rope_rel_rms_err_full"] < 4e-3
    # the counter against its closed form: sum_t min(t + 1, 16) of 64 * 65 / 2
    assert check["kept_pair_pct"] == pytest.approx(
        {"attention": 100.0, "window": 100 * (136 + 48 * 16) / 2080})
    assert [s["steps"] for s in check["by_sync"]][:2] == [3, 5]
    # the timed step's own call: the step after the window's, its loss, the
    # gradient it applied and the weights it left
    assert check["step"] == line["window"]["steps"] + 3 + 1
    assert set(check["update_rel_err"]) == set(adapter.GRAD_TOLS)
    assert max(check["update_rel_err"].values()) < adapter.UPDATE_REL_ERR_TOL
    # (D) measured: off the chip the dot path computes every pair
    assert check["computed_pair_pct"]["window"] == pytest.approx(
        100 * 64 * 64 / 2080)
    assert line["window"]["compiles"] == 0
    assert line["failed"] == 0
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}


def _window_plus_one(monkeypatch):
    """The MODEL hands its attention one key more than the window."""
    import dataclasses
    from hetu_tpu.models import transformer as tfm
    monkeypatch.setitem(tfm._KINDS, "window", dataclasses.replace(
        tfm._KINDS["window"],
        mixer=lambda h, p, cfg, mesh, attn_bias=None: tfm._attention(
            h, p, tfm._window_view(cfg), mesh, attn_bias,
            window=cfg.window.window + 1)))


def _state_left_unchanged(monkeypatch):
    """The step returns the weights and moments it was given."""
    from hetu_tpu.models import transformer as tfm
    monkeypatch.setattr(tfm, "adamw_update", lambda params, grads, opt, lr: (
        params, {**opt, "t": opt["t"] + 1.0}))


@pytest.mark.parametrize("wrong,names", [
    (_window_plus_one, ("own_window_edge_share",)),
    (_state_left_unchanged, ("grad_rel_rms_err", "update_rel_err"))],
    ids=["window_plus_one", "state_left_unchanged"])
def test_the_check_holds_the_timed_step(root, on_cpu, capsys, monkeypatch,
                                        wrong, names):
    """What the check compares comes out of the job's own step and the
    model's own layer functions: a window layer handed 17 keys fails by the
    share of the 17th key's effect in its mixer's output (1, for 0: a side
    call to the kernel with the right window would not see it), and a step
    that leaves its state as it was reads 1 in every class of gradient and
    of update."""
    _shrink(root, "laguna-xs.2", "pretrain-seq16384-b1-ep8share", TOY,
            {"sequences": 2, "seq_len": 64, "sync_every": 2,
             "warmup_steps": 3})
    adapter = manifest.adapter(manifest.resolve(str(root), CELL))
    wrong(monkeypatch)
    main.main(["--workload", CELL, "--seed", str(2 ** 31 + 49), "--seconds",
               "0.5", "--trace", "0"], root=str(root), t0=0.0)
    line = _last_line(capsys)
    check = line["check"]
    assert not line["correct"] and not check["ok"]
    limits = {"own_window_edge_share": adapter.OWN_WINDOW_EDGE_TOL,
              "grad_rel_rms_err": adapter.GRAD_TOLS,
              "update_rel_err": dict.fromkeys(adapter.GRAD_TOLS,
                                              adapter.UPDATE_REL_ERR_TOL)}
    for name in names:
        got = check[name]
        if isinstance(got, dict):
            assert all(got[k] == pytest.approx(1.0)
                       and limits[name][k] < 1.0 for k in got), got
        else:
            assert got == pytest.approx(1.0, abs=0.01), got
            assert limits[name] < 1.0
    # and nothing else of part (C) moved
    assert check["own_out_rel_rms_err_full"] < 1e-2
    assert check["own_rope_rel_rms_err_window"] < 4e-3


def test_laguna_cell_resolves_with_its_per_layer_metrics():
    cell = manifest.resolve(ROOT, CELL)
    assert cell.chips == 1 and cell.traffic["generator"] == "lm_zipf"
    t = cell.traffic
    assert (t["sequences"], t["seq_len"], t["zipf_exponent"], t["batches"],
            t["sync_every"], t["warmup_steps"], t["trace_steps"],
            t["check_sequences"], t["throughput_metric"]) == (
        1, 16384, 1.1, 8, 10, 15, 5, 1, "tokens_per_s")
    names = {m["name"] for m in cell.per_layer}
    # `<=`: a later PR may add a metric to this cell
    assert SWA_METRICS | {
        "compiles_in_window.tokens", "device_idle_pct.tokens",
        "peak_hbm_gib.tokens", "mfu_pct", "fwd_ms_per_step.tokens",
        "recompute_ms_per_step.tokens", "bwd_ms_per_step.tokens",
        "opt_ms_per_step.tokens", "flash_attn_time_pct.tokens",
        "mosaic_time_pct.tokens", "moe_time_pct.tokens",
        "moe_experts_ms_per_step.tokens", "moe_load_max_over_mean",
        "moe_route_dispatch_combine_ms_per_step.tokens",
        "moe_held_pick_pct",
        "moe_held_experts_roofline_pct.tokens"} <= names
    # its count is BERT's dense one: not this cell's
    assert "flash_attn_roofline_pct.tokens" not in names
    # its reader (`reduce/mla.py:reduce_mla`) returns nothing without a
    # `hetu_mla_*` scope, so a traced run of this cell cannot report it
    assert mla.reduce_mla(_fixture(), 1) is None
    assert "moe_shared_ms_per_step.tokens" not in names
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "setup_s"}
    for m in cell.per_layer:
        assert callable(manifest.reader(cell, m["name"]).read)
    # the catalog row's keys, the cut, and nothing else changed
    c = cell.config
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Laguna-XS.2")
    assert c["source"] == row["source_url"]
    published = row["config"]
    cut = {"num_hidden_layers": 5, "num_experts": 32, "vocab_size": 12544,
           **{k: published[k][:5] for k in (
               "layer_types", "mlp_layer_types",
               "num_attention_heads_per_layer")}}
    assert {k: c[k] for k in published} == {**published, **cut}
    assert c["layer_types"] == ["full_attention"] + [
        "sliding_attention"] * 3 + ["full_attention"]
    assert c["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    assert (c["num_routed_experts"], c["first_expert_held"]) == (256, 0)
    assert list(c["reduced"]) == ["num_hidden_layers", "num_experts",
                                  "vocab_size"]
    for key, said in (("num_hidden_layers", "40"), ("num_experts", "256"),
                      ("vocab_size", "100,352")):
        assert f"published {said}" in c["reduced"][key], key
    assert "8 CHIPS" in c["deployment"]
    assert "33.44B" in c["assumed"]["gating"]
    assert "shared_expert_gate" in c["assumed"]["gating"]
    assert "DeepSeek-V3" in c["assumed"]["router"]
    assert c["assumed"]["learning_rate"] == 3e-06
    assert c["assumed"]["expert_bias_update_rate"] == 0.03
    # the manifest's entry names the same cuts (the three lists with depth)
    entry = next(e for e in manifest.load(ROOT)["configs"]
                 if e["name"] == "laguna-xs.2")
    assert set(entry["reduced"]) == set(cut) and entry["source"] == c["source"]
    # no other cell reports this configuration's metrics
    for other in ("olmoe-1b-7b.pretrain-seq4096",
                  "keye-vl-2.0-30b-a3b.pretrain-seq16384-ep8share"):
        assert not SWA_METRICS & {
            m["name"] for m in manifest.resolve(ROOT, other).per_layer}


def test_step_flops_and_pair_counts_by_hand():
    c = manifest.resolve(ROOT, CELL).config
    T, W = 16384, 512
    # the pairs: a window keeps sum_t min(t + 1, W), by the loop and closed
    for seq, w in ((64, 16), (512, 512), (300, 512), (T, W)):
        assert swa.kept_pairs(seq, w) == sum(min(t + 1, w)
                                             for t in range(seq))
    assert swa.kept_pairs(T, W) == 8257792
    assert swa.kept_pairs(T) == T * (T + 1) / 2 == 134225920
    assert round(100 * 8257792 / 134225920, 2) == 6.15
    assert swa.layers_of(c) == {"full_attention": (2, 48),
                                "sliding_attention": (3, 64)}
    # the forward pass of the sequence, part by part, as ISSUE 49 counts it
    f = swa.forward_flops(c, T)
    assert f["full_attention_core"] == 2 * 4 * 128 * 48 * 134225920
    assert f["sliding_attention_core"] == 3 * 4 * 128 * 64 * 8257792
    D, d = 2048, 128
    proj = lambda h: 2 * D * (h * d + 2 * 8 * d) + 2 * h * d * D + 2 * D * h
    assert f["attention_proj_and_gate"] == T * (2 * proj(48) + 3 * proj(64))
    assert f["dense_mlp"] == T * 6 * D * 8192
    assert f["experts"] == T * 4 * (2 * D * 256 + 6 * D * 512
                                    + 8 * 32 / 256 * 6 * D * 512)
    assert f["head"] == T * 2 * D * 12544
    total = sum(f.values())
    assert round(total / 1e12, 1) == 16.4
    assert round(100 * f["full_attention_core"] / total) == 40
    assert round(100 * f["sliding_attention_core"] / total, 1) == 4.9
    # computed densely the window cores would be 13.2 TFLOP
    assert round(3 * 4 * 128 * 64 * 134225920 / 1e12, 1) == 13.2
    cores = f["full_attention_core"] + f["sliding_attention_core"]
    assert swa.laguna_train_flops_per_token(c, T) == pytest.approx(
        (3 * (total - cores) + 3.5 * cores) / T)
    # a call's required work and bytes: compute-bound at these shapes
    assert swa.attn_fwd_flops(1, 64, 8257792, 128) == 4 * 64 * 128 * 8257792
    assert swa.attn_bwd_flops(1, 64, 8257792, 128) == 2.5 * swa.attn_fwd_flops(
        1, 64, 8257792, 128)
    assert swa.attn_fwd_bytes(1, 64, 8, T, 128) == T * (
        2 * 128 * 2 * 72 + 4 * 64)
    assert swa.attn_bwd_bytes(1, 64, 8, T, 128) == T * (
        2 * 128 * 4 * 72 + 4 * 64)
    assert (swa.attn_fwd_flops(1, 64, 8257792, 128) / 197e12
            > swa.attn_fwd_bytes(1, 64, 8, T, 128) / 819e9)


def test_computed_pair_counter_reader():
    cell = manifest.resolve(ROOT, CELL)
    run = {"cell": cell, "trace": None, "counters": {"attn_pairs": {
        "window": {"kept": 8257792, "computed": 63 * 512 * 512},
        "attention": {"kept": 134225920, "computed": 138412032}}}}
    read = manifest.reader(cell, "swa_computed_pair_pct").read
    assert read(run) == pytest.approx(100 * 63 * 512 * 512 / 8257792)
    assert 199 < read(run) <= 200
    # a program that counts none (the parent of PR 49): nothing, no raise
    assert read({**run, "counters": {}}) is None
    assert read({**run, "counters": {"attn_pairs": {}}}) is None


def _phase(op_name):
    if "transpose(" not in op_name:
        return "fwd"
    return "recompute" if "rematted_computation" in op_name else "bwd"


def test_swa_table_from_the_fixture():
    """Every expected number is worked out here from the fixture's lines:
    the three scopes are found in forward, recomputed and backward ops, the
    rotation INSIDE `hetu_blk_qkv`; the flash kernels are told apart by the
    scope they run under."""
    fx = _fixture()
    ops = fx["chips"][0]["ops"]
    table = swa.reduce_swa(fx, steps=1)
    under = [op for op in ops if swa.scope_of(op[3])]
    flash = [op for op in ops if "flash_" in op[0].split(" = ")[0]]
    assert under and flash and len(under) < len(ops)
    total = sum(op[2] for op in ops)
    assert table["device_self_ms_per_step"] == pytest.approx(total / 1e6)
    for scope in swa.SCOPES:
        for p in swa.PHASES:
            want = sum(op[2] for op in under
                       if swa.scope_of(op[3]) == scope
                       and _phase(op[3]) == p) / 1e6
            assert want > 0, (scope, p)
            assert table["scope_ms_per_step"][scope][p] == pytest.approx(
                want), (scope, p)
    assert table["time_pct"] == pytest.approx(100 * sum(
        op[2] for op in under if swa.scope_of(op[3]) == swa.SWA) / total)
    for op in under:
        if swa.scope_of(op[3]) == swa.ROPE:
            assert "hetu_blk_qkv/hetu_attn_rope/" in op[3]
    # the kernels by the scope they run under: 64-head calls a window
    # layer's, 48-head calls a full layer's
    window = [op for op in flash if "/hetu_swa_attn/" in op[3]]
    full = [op for op in flash if "/hetu_blk_attn/" in op[3]]
    assert window and full and len(window) + len(full) == len(flash)
    assert all("bf16[1,16384,8192]" in op[0] for op in window)
    assert all("bf16[1,16384,6144]" in op[0] for op in full)
    for which, found in ((swa.WINDOW, window), (swa.FULL, full)):
        f = table["flash"][which]
        assert f["seconds"] == pytest.approx(sum(op[2] for op in found) / 1e9)
        assert f["fwd_calls"] == sum(
            op[0].split(" = ")[0].lstrip("%").startswith("flash_fwd")
            for op in found) > 0
        assert f["bwd_calls"] == sum(
            op[0].split(" = ")[0].lstrip("%").startswith("flash_bwd")
            for op in found) > 0
    text = swa.render(table)
    assert "hetu_attn_gate" in text and "sliding_attention flash_fwd" in text
    # the older reader sees the rotation as the block's projection time
    from benchmark.reduce import block
    assert {block.scope_of(op[3]) for op in under
            if swa.scope_of(op[3]) == swa.ROPE} == {"hetu_blk_qkv"}
    # a trace without the scopes reads as nothing
    assert swa.reduce_swa(_fixture("dsa_one_chip.json"), steps=1) is None


def test_swa_readers_on_a_traced_run_of_the_fixture(monkeypatch):
    """The six readers through `for_run`, as the harness calls them."""
    cell = manifest.resolve(ROOT, CELL)
    table = swa.reduce_swa(_fixture(), steps=1)
    monkeypatch.setattr(swa, "for_run", lambda run: table)
    run = {"cell": cell, "trace": {"steps": 1},
           "device": {"kind": "TPU v5 lite"},
           "counters": {"attn_pairs": {"window": {
               "kept": 8257792, "computed": 63 * 512 * 512}}}}
    read = lambda name: manifest.reader(cell, name).read(run)
    by = table["scope_ms_per_step"]
    assert read("swa_time_pct.tokens") == pytest.approx(table["time_pct"])
    assert read("attn_rope_ms_per_step.tokens") == pytest.approx(
        sum(by[swa.ROPE].values()))
    assert read("attn_gate_ms_per_step.tokens") == pytest.approx(
        sum(by[swa.GATE].values()))
    for name, which, heads, pairs in (
            ("swa_attn_roofline_pct.tokens", swa.WINDOW, 64, 8257792),
            ("full_attn_roofline_pct.tokens", swa.FULL, 48, 134225920)):
        f = table["flash"][which]
        want = 100 * (f["fwd_calls"] * 4 + f["bwd_calls"] * 10) * (
            128 * heads * pairs) / f["seconds"] / 197e12
        assert read(name) == pytest.approx(want)
        # the kernels' own times on the chip, whole calls: a share of peak
        assert 5 < want < 100, (name, want)
    assert read("swa_computed_pair_pct") == pytest.approx(
        100 * 63 * 512 * 512 / 8257792)
    # without the scopes (the parent of PR 49, any other model): nothing
    monkeypatch.setattr(swa, "for_run", lambda run: None)
    run["counters"] = {}
    for name in SWA_METRICS:
        assert read(name) is None, name
    # an end-to-end run has no trace
    monkeypatch.undo()
    assert swa.for_run({"cell": cell, "trace": None}) is None
