"""CPU tests of what ISSUE 39 added to the benchmark: the kanana-2-30b-a3b
adapter at a toy size against its reference, the cell and its files, the
step's and the kernels' FLOPs against hand counts, and reduce/mla.py with its
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
from benchmark.reduce import mla, moe                  # noqa: E402
from benchmark.tests.test_benchmark import (           # noqa: E402,F401
    _last_line, _shrink, on_cpu, root)

CELL = "kanana-2-30b-a3b.pretrain-seq8192-ep8share"
MLA_METRICS = {"mla_time_pct.tokens", "mla_proj_ms_per_step.tokens",
               "mla_kv_up_ms_per_step.tokens",
               "moe_shared_ms_per_step.tokens",
               "mla_attn_roofline_pct.tokens"}
TOY = {"hidden_size": 64, "intermediate_size": 128,
       "moe_intermediate_size": 48, "num_attention_heads": 4,
       "num_key_value_heads": 4, "kv_lora_rank": 32, "qk_nope_head_dim": 32,
       "qk_rope_head_dim": 16, "qk_head_dim": 48, "v_head_dim": 24,
       "head_dim": 16, "n_routed_experts": 2, "num_routed_experts": 8,
       "first_expert_held": 2, "num_experts_per_tok": 2, "vocab_size": 512,
       "max_position_embeddings": 64}


def _fixture(name="mla_one_chip.json"):
    with open(os.path.join(HERE, "fixtures", name)) as f:
        return json.load(f)


def test_kanana_adapter_runs_and_agrees_with_reference(root, on_cpu, capsys):
    """The real structure at a toy size: latent attention + dense, then 4 x
    latent attention + experts with the shared expert, 2 of 8 experts held
    from expert 2 on, top 2, heads of 48 / 24 columns."""
    _shrink(root, "kanana-2-30b-a3b", "pretrain-seq8192-ep8share", TOY,
            {"sequences": 2, "seq_len": 32, "sync_every": 2,
             "warmup_steps": 3})
    rc = main.main(["--workload", CELL, "--seed", str(2 ** 31 + 39),
                    "--seconds", "0.5", "--trace", "0"],
                   root=str(root), t0=0.0)
    assert rc == 0
    line = _last_line(capsys)
    check = line["check"]
    assert line["correct"], check
    assert set(check["hidden_rel_rms_err"]) == {"after_mla+dense_run",
                                                "after_stack"}
    assert set(check["grad_rel_rms_err"]) == {
        "ln1_scale", "ln2_scale", "kv_norm", "lnf_scale", "router", "wq",
        "wkv_a", "wkv_b", "wo", "dense_w1_layer0", "shared_w1", "shared_w2",
        "expert_w1_layer1", "expert_w2_layer1"}
    # four expert layers; the compute dtype is bfloat16 here too, so a
    # near-tie may flip, and a bias entry only where such flips explain it
    assert len(check["same_expert_share"]) == 4
    assert min(check["same_expert_share"]) >= 0.93
    assert check["bias_entries_unexplained"] == 0
    assert check["bias_gradient_picks_moved_share"] == 0.0
    assert check["dropped_picks"] == 0
    assert abs(sum(check["held_picks"])
               - sum(check["reference_held_picks"])) <= 4
    assert 0 < sum(check["held_picks"]) < 4 * 32 * 2
    assert check["routed_experts"] == 8
    # float32 parts against float64, on the CPU: float32 rounding (the
    # forward kernel interpreted, at 48 / 24 columns a head)
    for key in ("own_score_abs_err", "own_weight_rel_err",
                "own_latent_rel_rms_err", "own_lse_abs_err"):
        assert check[key] < 1e-5, (key, check[key])
    # the counter read at every sync, the warm-up's first
    assert [s["steps"] for s in check["by_sync"]][:2] == [3, 5]
    assert all(0 <= s["held_pick_pct"] <= 100 for s in check["by_sync"])
    assert check["grad_tokens"] == 32
    assert line["window"]["compiles"] == 0
    assert line["failed"] == 0
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}


def test_kanana_cell_resolves_with_its_per_layer_metrics():
    cell = manifest.resolve(ROOT, CELL)
    assert cell.chips == 1 and cell.traffic["generator"] == "lm_zipf"
    t = cell.traffic
    assert (t["seq_len"], t["zipf_exponent"], t["batches"], t["sync_every"],
            t["warmup_steps"], t["trace_steps"], t["check_sequences"]) == (
        8192, 1.1, 8, 10, 15, 5, 1)
    assert t["sequences"] in (2, 3, 4)
    names = {m["name"] for m in cell.per_layer}
    # `<=`: a later PR may add a metric to this cell
    assert MLA_METRICS | {
        "compiles_in_window.tokens", "device_idle_pct.tokens",
        "peak_hbm_gib.tokens", "mfu_pct", "fwd_ms_per_step.tokens",
        "recompute_ms_per_step.tokens", "bwd_ms_per_step.tokens",
        "opt_ms_per_step.tokens", "flash_attn_time_pct.tokens",
        "mosaic_time_pct.tokens", "moe_time_pct.tokens",
        "moe_experts_ms_per_step.tokens", "moe_load_max_over_mean",
        "moe_route_dispatch_combine_ms_per_step.tokens",
        "moe_held_pick_pct", "moe_held_experts_roofline_pct.tokens"} <= names
    # their readers count one head width, every key of a causal call, or
    # all S*k picks at the dense width: not this cell's
    assert not {"moe_experts_roofline_pct.tokens",
                "flash_attn_roofline_pct.tokens"} & names
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "setup_s"}
    for m in cell.per_layer:
        assert callable(manifest.reader(cell, m["name"]).read)
    # the catalog row's keys, the three cuts, and nothing else changed
    c = cell.config
    assert list(c["reduced"]) == ["num_hidden_layers", "n_routed_experts",
                                  "vocab_size"]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "kanana-2-30b-a3b-instruct-2601")
    assert c["source"] == row["source_url"]
    cut = {"num_hidden_layers": 5, "n_routed_experts": 16,
           "vocab_size": 16128}
    assert {k: c[k] for k in row["config"]} == {**row["config"], **cut}
    assert (c["num_routed_experts"], c["first_expert_held"]) == (128, 0)
    for key, published in (("num_hidden_layers", "48"),
                           ("n_routed_experts", "128"),
                           ("vocab_size", "128,256")):
        assert f"published {published}" in c["reduced"][key], key
    assert "8 CHIPS" in c["deployment"]
    assert c["assumed"]["learning_rate"] == 3e-06
    assert c["assumed"]["expert_bias_update_rate"] == 0.03
    # the manifest's entry names the same three cuts
    entry = next(e for e in manifest.load(ROOT)["configs"]
                 if e["name"] == "kanana-2-30b-a3b")
    assert entry["reduced"] == list(cut) and entry["source"] == c["source"]
    # no other cell reports this configuration's metrics
    for other in ("olmoe-1b-7b.pretrain-seq4096",
                  "lfm2-8b-a1b.pretrain-seq8192-ep4load"):
        assert not MLA_METRICS & {
            m["name"] for m in manifest.resolve(ROOT, other).per_layer}


def test_step_and_kernel_flops_by_hand():
    """At the cell's shape (8,192 tokens a sequence, D 2048), by hand."""
    c = manifest.resolve(ROOT, CELL).config
    D, T, H = 2048, 8192, 32
    projections = 2 * (D * H * 192 + D * 576 + 512 * H * 256 + H * 128 * D)
    core = T * H * (192 + 128)
    dense = 6 * D * 6144
    shared = 6 * D * 1536
    # 0.75 held picks a token a layer: 6 picks x 16 of 128 experts
    experts = 2 * D * 128 + shared + 0.75 * 6 * D * 768
    head = 2 * D * 16128
    per_token = mla.kanana_train_flops_per_token(c, T)
    assert per_token == pytest.approx(
        3 * (5 * (projections + core) + dense + 4 * experts + head))
    assert round(per_token / 1e9, 2) == 2.79
    fwd = per_token / 3
    assert round(fwd / 1e6) == 930
    shares = {"projections": 5 * projections, "core": 5 * core,
              "dense": dense, "shared": 4 * shared, "head": head}
    assert {k: round(100 * v / fwd) for k, v in shares.items()} == {
        "projections": 28, "core": 45, "dense": 8, "shared": 8, "head": 7}
    # the uncut model's layer counts every pick: 6 a token
    whole = {**c, "n_routed_experts": 128, "num_routed_experts": 128}
    assert (mla.kanana_train_flops_per_token(whole, T) - per_token
            ) == pytest.approx(3 * 4 * 5.25 * 6 * D * 768)

    # the kernels: P causal pairs a (batch row, head), a multiply-add each
    # a pair and column; forward the two products, backward the five
    P = T * (T + 1) / 2
    assert mla.causal_pairs(T) == P
    assert mla.mla_attn_fwd_flops(4, H, T, 192, 128) == (
        2 * 4 * H * P * (192 + 128))
    assert mla.mla_attn_bwd_flops(4, H, T, 192, 128) == (
        2 * 4 * H * P * (192 + 192 + 192 + 128 + 128))
    # at one width they are half of `kernel_flops`' every-key counts, and a
    # diagonal's worth more
    from benchmark.reduce import kernel_flops
    assert mla.mla_attn_fwd_flops(1, 1, T, 128, 128) == pytest.approx(
        kernel_flops.flash_fwd_flops(1, T, 128) / 2 * (T + 1) / T)
    assert mla.mla_attn_bwd_flops(1, 1, T, 128, 128) == pytest.approx(
        kernel_flops.flash_bwd_flops(1, T, 128) / 2 * (T + 1) / T)
    # 10 forward calls (5 layers, each run again under `remat`) and 5
    # backward in 1 s of kernel time
    flash = {"seconds": 1.0, "fwd_calls": 10, "bwd_calls": 5}
    want = 100 * (10 * 2 * 4 * H * P * 320 + 5 * 2 * 4 * H * P * 832) / 197e12
    assert mla.attn_roofline_pct(
        flash, c, {"sequences": 4, "seq_len": T},
        "TPU v5 lite") == pytest.approx(want)
    assert mla.attn_roofline_pct({**flash, "seconds": 0.0}, c,
                                 {"sequences": 4, "seq_len": T},
                                 "TPU v5 lite") is None


# -- reduce/mla.py on a fixture cut from a v5e trace --------------------------

def _phase(op_name):
    if "transpose(" not in op_name:
        return "fwd"
    return "recompute" if "rematted_computation" in op_name else "bwd"


def test_mla_table_from_the_fixture():
    """Every expected number is worked out here from the fixture's lines:
    the three `hetu_mla_*` scopes are found in forward, recomputed and
    backward ops, each INSIDE `hetu_blk_qkv`; the shared expert's scope is
    its own; the flash kernels are counted by name at their two widths."""
    fx = _fixture()
    ops = fx["chips"][0]["ops"]
    table = mla.reduce_mla(fx, steps=1)
    under = [op for op in ops if mla.scope_of(op[3])]
    flash = [op for op in ops if "flash_" in op[0].split(" = ")[0]]
    assert under and flash and len(under) + len(flash) < len(ops)
    # the fixture's ops do not nest: self time is duration
    total = sum(op[2] for op in ops)
    assert table["device_self_ms_per_step"] == pytest.approx(total / 1e6)
    projections = [op for op in under if mla.scope_of(op[3]) in mla.MLA]
    assert table["mla_ms_per_step"] == pytest.approx(
        sum(op[2] for op in projections + flash) / 1e6)
    assert table["time_pct"] == pytest.approx(
        100 * sum(op[2] for op in projections + flash) / total)
    for scope in mla.SCOPES:
        for p in mla.PHASES:
            want = sum(op[2] for op in under
                       if mla.scope_of(op[3]) == scope
                       and _phase(op[3]) == p) / 1e6
            assert want > 0, (scope, p)
            assert table["scope_ms_per_step"][scope][p] == pytest.approx(
                want), (scope, p)
    for op in projections:
        assert f"hetu_blk_qkv/{mla.scope_of(op[3])}/" in op[3]
    shared = [op for op in under if mla.scope_of(op[3]) == mla.SHARED]
    assert all("/hetu_moe_shared/hetu_blk_mlp_" in op[3] for op in shared)
    assert not [op for op in shared if moe.scope_of(op[3])]
    # the projections keep their einsum in the path
    assert any("hetu_mla_kv_up/btd,de->bte" in op[3] for op in under)
    # the kernels: q's gradient comes back 6,144 wide, v's 4,096
    names = {op[0].split(" = ")[0].lstrip("%").rsplit(".", 1)[0]
             for op in flash}
    assert names == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    f = table["flash"]
    assert f["seconds"] == pytest.approx(sum(op[2] for op in flash) / 1e9)
    assert f["fwd_calls"] == sum("flash_fwd" in op[0].split(" = ")[0]
                                 for op in flash)
    assert f["bwd_calls"] == sum("flash_bwd_dq" in op[0].split(" = ")[0]
                                 for op in flash)
    assert any("bf16[4,8192,6144]" in op[0] for op in flash)
    assert any(op[0].startswith("%flash_fwd") and "bf16[4,8192,4096]"
               in op[0] for op in flash)
    text = mla.render(table)
    assert "hetu_mla_kv_up" in text and "flash_bwd_dkv" in text
    # the older readers see the projections as the block's, and the shared
    # expert as MLP time outside the four MoE scopes
    from benchmark.reduce import block
    assert {block.scope_of(op[3]) for op in projections} == {"hetu_blk_qkv"}
    assert {block.scope_of(op[3]) for op in shared} <= {
        "hetu_blk_mlp_up", "hetu_blk_mlp_down"}
    # a trace without the scopes reads as nothing
    other = _fixture("sconv_one_chip.json")
    assert mla.reduce_mla(other, steps=1) is None


def test_mla_readers_on_a_traced_run_of_the_fixture(monkeypatch):
    """The five readers through `for_run`, as the harness calls them."""
    cell = manifest.resolve(ROOT, CELL)
    fx = _fixture()
    table = mla.reduce_mla(fx, steps=1)
    monkeypatch.setattr(mla, "for_run", lambda run: table)
    run = {"cell": cell, "trace": {"steps": 1},
           "device": {"kind": "TPU v5 lite"}, "counters": {}}
    read = lambda name: manifest.reader(cell, name).read(run)
    by = table["scope_ms_per_step"]
    assert read("mla_time_pct.tokens") == pytest.approx(table["time_pct"])
    assert read("mla_proj_ms_per_step.tokens") == pytest.approx(
        sum(sum(by[s].values()) for s in mla.MLA))
    assert read("mla_kv_up_ms_per_step.tokens") == pytest.approx(
        sum(by[mla.KV_UP].values()))
    assert read("moe_shared_ms_per_step.tokens") == pytest.approx(
        sum(by[mla.SHARED].values()))
    f = table["flash"]
    t = cell.traffic
    shape = (t["sequences"], 32, 8192, 192, 128)
    want = 100 * (f["fwd_calls"] * mla.mla_attn_fwd_flops(*shape)
                  + f["bwd_calls"] * mla.mla_attn_bwd_flops(*shape)
                  ) / f["seconds"] / 197e12
    assert read("mla_attn_roofline_pct.tokens") == pytest.approx(want)
    # the kernels' own times on the chip, whole calls: a share of the peak
    assert 20 < want < 100
    # without the scopes (the parent of PR 39, any other model): nothing
    monkeypatch.setattr(mla, "for_run", lambda run: None)
    for name in MLA_METRICS:
        assert read(name) is None, name
    # an end-to-end run has no trace
    monkeypatch.undo()
    assert mla.for_run({"cell": cell, "trace": None}) is None
