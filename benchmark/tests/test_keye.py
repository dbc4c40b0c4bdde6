"""CPU tests of what ISSUE 44 added to the benchmark: the keye-vl-2.0-30b-a3b
adapter at a toy size against its reference (both parts of its check), the
cell and its files, the step's and the kernels' FLOPs against hand counts,
and reduce/dsa.py with its eight readers on a fixture cut from a TPU v5e trace
of the cell. No number here is a device number."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import main, manifest          # noqa: E402
from benchmark.reduce import dsa                       # noqa: E402
from benchmark.tests.test_benchmark import (           # noqa: E402,F401
    _last_line, _shrink, on_cpu, root)

CELL = "keye-vl-2.0-30b-a3b.pretrain-seq16384-ep8share"
DSA_METRICS = {"dsa_time_pct.tokens", "dsa_index_ms_per_step.tokens",
               "dsa_select_ms_per_step.tokens", "dsa_loss_ms_per_step.tokens",
               "dsa_kept_pair_pct", "dsa_attn_roofline_pct.tokens",
               "dsa_index_roofline_pct.tokens",
               "dsa_loss_roofline_pct.tokens"}
TOY = {"hidden_size": 32, "intermediate_size": 64,
       "moe_intermediate_size": 24, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 16, "num_experts": 2,
       "num_local_experts": 2, "num_routed_experts": 8,
       "first_expert_held": 2, "num_experts_per_tok": 2, "vocab_size": 512,
       "max_position_embeddings": 256,
       "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default"},
       "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4,
                     "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                     "q_chunk_size": 512, "topk": 16}}


def _fixture(name="dsa_one_chip.json"):
    with open(os.path.join(HERE, "fixtures", name)) as f:
        return json.load(f)


def test_keye_adapter_runs_and_agrees_with_reference(root, on_cpu, capsys,
                                                     monkeypatch):
    """The real structure at a toy size: 4 layers of grouped-query attention
    under an indexer that keeps 16 of 64 keys, 2 of 8 experts held from
    expert 2 on, top 2, heads of 16 columns on a 32-wide stream; gradients on
    the first 32 tokens, where the selection bites too."""
    _shrink(root, "keye-vl-2.0-30b-a3b", "pretrain-seq16384-ep8share", TOY,
            {"sequences": 2, "seq_len": 64, "sync_every": 2})
    adapter = manifest.adapter(manifest.resolve(str(root), CELL))
    rc = main.main(["--workload", CELL, "--seed", str(2 ** 31 + 44),
                    "--seconds", "0.5", "--trace", "0"],
                   root=str(root), t0=0.0)
    assert rc == 0
    line = _last_line(capsys)
    check = line["check"]
    assert line["correct"], check
    assert set(check["hidden_rel_rms_err"]) == {"after_layer_0",
                                                "after_stack"}
    assert set(check["grad_rel_rms_err"]) == set(adapter.GRAD_TOLS) == {
        "ln1_scale", "ln2_scale", "q_norm", "k_norm", "lnf_scale", "router",
        "wq", "wk", "wv", "wo", "wq_idx", "wk_idx", "ww_idx",
        "k_idx_norm_scale", "k_idx_norm_bias", "expert_w1_layer1",
        "expert_w2_layer1"}
    # part (A): four layers' L_I against the reference given the system's
    # kept sets and picks
    assert len(check["index_loss"]) == 4 and min(check["index_loss"]) > 0
    assert max(check["index_loss_rel_err"]) < 5e-2
    # part (B): every checked key against float64 scores; the counter
    # against its closed form, sum_t min(t + 1, 16) of 64 * 65 / 2
    assert check["kept_keys_checked"] == 4 * (136 + 48 * 16)
    assert check["kept_differ_share"] <= adapter.KEPT_DIFFER_MAX_SHARE
    # and every token's picks against float64 logits on the router's own rows
    assert check["picks_checked"] == 4 * 64 * 2
    assert check["picks_differ_share"] <= adapter.PICKS_DIFFER_MAX_SHARE
    assert check["kept_pair_pct"] == pytest.approx(
        [100 * (136 + 48 * 16) / 2080] * 4)
    assert check["dropped_picks"] == 0
    assert 0 < sum(check["held_picks"]) < 4 * 64 * 2
    # the chip is the member (2 of 8 experts: four members) whose share of
    # the first batch's picks is nearest the even one
    shares = check["member_shares"]
    assert len(shares) == 4 and sum(shares) == pytest.approx(1.0)
    assert abs(shares[check["member"]] - 0.25) == min(
        abs(x - 0.25) for x in shares)
    assert check["grad_tokens"] == 64 and check["sample"] == [1, 64]
    assert line["window"]["compiles"] == 0
    assert line["failed"] == 0
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}


def test_keye_cell_resolves_with_its_per_layer_metrics():
    cell = manifest.resolve(ROOT, CELL)
    assert cell.chips == 1 and cell.traffic["generator"] == "lm_zipf"
    t = cell.traffic
    assert (t["sequences"], t["seq_len"], t["zipf_exponent"], t["batches"],
            t["sync_every"], t["warmup_steps"], t["trace_steps"],
            t["check_sequences"], t["throughput_metric"]) == (
        2, 16384, 1.1, 8, 5, 3, 5, 1, "tokens_per_s")
    names = {m["name"] for m in cell.per_layer}
    # `<=`: a later PR may add a metric to this cell
    assert DSA_METRICS | {
        "compiles_in_window.tokens", "device_idle_pct.tokens",
        "peak_hbm_gib.tokens", "mfu_pct", "fwd_ms_per_step.tokens",
        "recompute_ms_per_step.tokens", "bwd_ms_per_step.tokens",
        "opt_ms_per_step.tokens", "flash_attn_time_pct.tokens",
        "mosaic_time_pct.tokens", "moe_time_pct.tokens",
        "moe_experts_ms_per_step.tokens", "moe_load_max_over_mean",
        "moe_route_dispatch_combine_ms_per_step.tokens",
        "moe_held_pick_pct", "moe_held_experts_roofline_pct.tokens"} <= names
    # their readers count every key of a causal call, all S*k picks at the
    # dense width, or pin their cells with `==`: not this cell's
    assert not {"moe_experts_roofline_pct.tokens",
                "flash_attn_roofline_pct.tokens", "head_ms_per_step.tokens",
                "step_named_pct.tokens",
                "block_attn_core_ms_per_step.tokens"} & names
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "setup_s"}
    for m in cell.per_layer:
        assert callable(manifest.reader(cell, m["name"]).read)
    # the catalog row's keys, the four cuts, and nothing else changed
    c = cell.config
    assert list(c["reduced"]) == ["num_hidden_layers", "num_experts",
                                  "num_local_experts", "vocab_size"]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Keye-VL-2.0-30B-A3B")
    assert c["source"] == row["source_url"]
    cut = {"num_hidden_layers": c["num_hidden_layers"], "num_experts": 16,
           "num_local_experts": 16, "vocab_size": 19072}
    assert c["num_hidden_layers"] in (4, 5)
    assert {k: c[k] for k in row["config"]} == {**row["config"], **cut}
    assert (c["num_routed_experts"], c["first_expert_held"]) == (128, 0)
    for key, published in (("num_hidden_layers", "48"),
                           ("num_experts", "128"),
                           ("num_local_experts", "128"),
                           ("vocab_size", "151,936")):
        assert f"published {published}" in c["reduced"][key], key
    assert "8 CHIPS" in c["deployment"]
    assert c["assumed"]["learning_rate"] == 3e-06
    assert c["assumed"]["indexer_loss_coef"] == 1.0
    # the manifest's entry names the same cuts
    entry = next(e for e in manifest.load(ROOT)["configs"]
                 if e["name"] == "keye-vl-2.0-30b-a3b")
    assert entry["reduced"] == list(cut) and entry["source"] == c["source"]
    # no other cell reports this configuration's metrics
    for other in ("olmoe-1b-7b.pretrain-seq4096",
                  "kanana-2-30b-a3b.pretrain-seq8192-ep8share"):
        assert not DSA_METRICS & {
            m["name"] for m in manifest.resolve(ROOT, other).per_layer}


def test_keye_step_and_kernel_flops_by_hand():
    """At the cell's shape (16,384 tokens a sequence, D 2048), by hand."""
    c = manifest.resolve(ROOT, CELL).config
    D, T, H, d, L = 2048, 16384, 32, 128, c["num_hidden_layers"]
    kept = 2048 * 2049 / 2 + (T - 2048) * 2048
    causal = T * (T + 1) / 2
    assert dsa.kept_pairs(T, 2048) == kept == 31_458_304
    assert round(100 * kept / causal, 1) == 23.4
    assert dsa.kept_pairs(1024, 2048) == 1024 * 1025 / 2     # every pair
    projections = 2 * D * (H * d + 2 * 4 * d) + 2 * H * d * D
    indexer = 2 * D * (16 * 64 + 64 + 16)
    # 1 held pick a token a layer: 8 picks x 16 of 128 experts
    experts = 2 * D * 128 + 1.0 * 6 * D * 768
    token = projections + indexer + experts
    assert round(token / 1e6, 1) == 52.2
    shares = dsa.keye_forward_shares(c, T)
    assert shares == {"attention": 4 * d * H * kept,
                      "index_scores": 2 * 64 * 16 * causal,
                      "loss_target": 2 * d * H * kept,
                      "rest": T * token}
    # TFLOP a sequence a layer: 0.52 kept attention (dense: 2.2), 0.27
    # index scores, 0.26 the loss's target, 0.86 everything else
    assert {k: round(v / 1e12, 2) for k, v in shares.items()} == {
        "attention": 0.52, "index_scores": 0.27, "loss_target": 0.26,
        "rest": 0.86}
    assert round(4 * d * H * causal / 1e12, 1) == 2.2
    sparse = (shares["attention"] + shares["index_scores"]
              + shares["loss_target"])
    assert sparse > shares["rest"]
    pairs = (14 * d * H * kept + 2 * 64 * 16 * causal + 4 * 64 * 16 * kept
             + 2 * d * H * kept) / T
    per_token = dsa.keye_train_flops_per_token(c, T)
    assert per_token == pytest.approx(
        L * (3 * token + pairs) + 3 * 2 * D * 19072)
    # the kernels: forward the two products a kept pair, backward the five
    assert dsa.attn_fwd_flops(2, H, kept, d) == 2 * 4 * d * H * kept
    assert dsa.attn_bwd_flops(2, H, kept, d) == 2 * 10 * d * H * kept
    # 2 L forward calls (each run again under `remat`) and L backward in 1 s
    flash = {"seconds": 1.0, "fwd_calls": 2 * L, "bwd_calls": L}
    want = 100 * (2 * L * 2 * 4 + L * 2 * 10) * d * H * kept / 197e12
    traffic = {"sequences": 2, "seq_len": T}
    assert dsa.attn_roofline_pct(flash, c, traffic,
                                 "TPU v5 lite") == pytest.approx(want)
    assert dsa.attn_roofline_pct({**flash, "seconds": 0.0}, c, traffic,
                                 "TPU v5 lite") is None
    # the three kernels of kernels/dsa.py: a pass is one sequence of one
    # layer in 32 blocks of 512 query rows; FLOPs bound all three
    assert dsa.row_blocks(T) == 32 and dsa.row_blocks(96) == 3
    keys = lambda pairs: pairs / 512       # a pair's key, once a row block
    fwd_bytes = T * 16 * (64 * 2 + 4) + keys(causal) * 64 * 2 + 4 * causal
    assert dsa.index_scores_bytes(16, 64, T, causal) == fwd_bytes
    assert dsa.index_scores_bwd_flops(16, kept, 64) == 4 * 64 * 16 * kept
    assert dsa.index_scores_bwd_bytes(16, 64, T, kept) == (
        T * 16 * (64 * 2 + 4) + keys(kept) * 64 * 2 + 4 * kept
        + 4 * T * 16 * 65 + 4 * keys(kept) * 64)
    assert dsa.loss_target_bytes(H, 4, d, T, kept) == (
        T * H * (d * 2 + 4) + keys(kept) * 4 * d * 2 + 4 * kept)
    fwd_s = 2 * 64 * 16 * causal / 197e12
    assert fwd_s > fwd_bytes / 819e9
    bwd_s = 4 * 64 * 16 * kept / 197e12
    probs_s = 2 * d * H * kept / 197e12
    assert [round(1e3 * s, 2) for s in (fwd_s, bwd_s, probs_s)] == [
        1.4, 0.65, 1.31]
    # 4 forward passes and 1 backward pass in 10 ms; 2 passes of the target
    kernels = {dsa.INDEX: {"calls": 4 * 32.0, "seconds": 8e-3},
               dsa.INDEX_BWD: {"calls": 32.0, "seconds": 2e-3},
               dsa.PROBS: {"calls": 64.0, "seconds": 1e-2}}
    assert dsa.index_roofline_pct(kernels, c, traffic, "TPU v5 lite") == (
        pytest.approx(100 * (4 * fwd_s + bwd_s) / 1e-2))
    assert dsa.loss_roofline_pct(kernels, c, traffic, "TPU v5 lite") == (
        pytest.approx(100 * 2 * probs_s / 1e-2))
    assert dsa.index_roofline_pct({}, c, traffic, "TPU v5 lite") is None
    assert dsa.loss_roofline_pct({dsa.INDEX: kernels[dsa.INDEX]}, c, traffic,
                                 "TPU v5 lite") is None


def test_kept_pair_counter_reader():
    cell = manifest.resolve(ROOT, CELL)
    read = manifest.reader(cell, "dsa_kept_pair_pct").read
    counted = {"kept_pairs": [62916608] * 4, "causal_pairs": [268451840] * 4}
    assert read({"counters": {"dsa": counted}}) == pytest.approx(
        100 * 31_458_304 / (16384 * 16385 / 2))
    assert read({"counters": {}}) is None


# -- reduce/dsa.py on a fixture cut from a v5e trace --------------------------

def _phase(op_name):
    if "transpose(" not in op_name:
        return "fwd"
    return "recompute" if "rematted_computation" in op_name else "bwd"


def test_dsa_table_from_the_fixture():
    """Every expected number is worked out here from the fixture's lines:
    the four `hetu_dsa_*` scopes are found in forward and recomputed ops (the
    projections in backward ops too; the selection has no backward and the
    loss's runs inside its forward rule), the INNERMOST scope names an op
    (the index scores inside the selection and inside the loss), and the
    flash kernels are counted by name."""
    fx = _fixture()
    ops = fx["chips"][0]["ops"]
    table = dsa.reduce_dsa(fx, steps=1)
    under = [op for op in ops if dsa.scope_of(op[3])
             and "flash_" not in op[0].split(" = ")[0]]
    flash = [op for op in ops if "flash_" in op[0].split(" = ")[0]]
    assert under and flash and len(under) + len(flash) < len(ops)
    # the fixture's ops do not nest: self time is duration
    total = sum(op[2] for op in ops)
    assert table["device_self_ms_per_step"] == pytest.approx(total / 1e6)
    assert table["dsa_ms_per_step"] == pytest.approx(
        sum(op[2] for op in under + flash) / 1e6)
    assert table["time_pct"] == pytest.approx(
        100 * sum(op[2] for op in under + flash) / total)
    seen = set()
    for scope in dsa.SCOPES:
        for p in dsa.PHASES:
            want = sum(op[2] for op in under
                       if dsa.scope_of(op[3]) == scope
                       and _phase(op[3]) == p) / 1e6
            assert table["scope_ms_per_step"][scope][p] == pytest.approx(
                want), (scope, p)
            if want:
                seen.add((scope, p))
    assert seen >= {(s, p) for s in dsa.SCOPES for p in ("fwd", "recompute")
                    } | {(dsa.PROJ, "bwd")}
    assert (dsa.SELECT, "bwd") not in seen and (dsa.LOSS, "bwd") not in seen
    # the index scores run inside the selection and inside the loss, and
    # are the index scores' either way; their kernels carry their names
    scores = [op for op in under if dsa.scope_of(op[3]) == dsa.SCORES]
    assert any(f"/{dsa.SELECT}/" in op[3] for op in scores)
    assert any(f"/{dsa.LOSS}/" in op[3] for op in scores)
    assert any("dsa_index_scores_bwd" in op[3] for op in scores)
    assert any("dsa_head_probs/pallas_call" in op[3] for op in under
               if dsa.scope_of(op[3]) == dsa.LOSS)
    # the kernels under the mask: the cell's own shapes
    names = {op[0].split(" = ")[0].lstrip("%").rsplit(".", 1)[0]
             for op in flash}
    assert names == {"flash_fwd", "flash_bwd_dqkv"}
    f = table["flash"]
    assert f["seconds"] == pytest.approx(sum(op[2] for op in flash) / 1e9)
    assert f["fwd_calls"] == sum("flash_fwd" in op[0].split(" = ")[0]
                                 for op in flash)
    assert f["bwd_calls"] == sum("flash_bwd_dqkv" in op[0].split(" = ")[0]
                                 for op in flash)
    assert all("hetu_blk_attn/flash_" in op[3] for op in flash)
    assert any("bf16[2,16384,4096]" in op[0] for op in flash)
    # the module's own three kernels, by the names a trace gives them (the
    # forward's under `jax.vjp` too), each also under its scope
    own = lambda *has: [op for op in ops if "pallas_call" in op[3] and all(
        h in op[3] for h in has)]
    for kernel, of in ((dsa.INDEX, [op for op in own("dsa_index_scores")
                                    if "_bwd" not in op[3]]),
                       (dsa.INDEX_BWD, own("dsa_index_scores_bwd")),
                       (dsa.PROBS, own("dsa_head_probs"))):
        assert of and table["kernels"][kernel] == {
            "calls": len(of), "seconds": pytest.approx(
                sum(op[2] for op in of) / 1e9)}, kernel
    assert any("jvp(dsa_index_scores)" in op[3] for op in ops)
    text = dsa.render(table)
    assert "hetu_dsa_select" in text and "flash_bwd_dqkv" in text
    assert "dsa_head_probs" in text
    # the older readers see none of the four as a part of the block
    from benchmark.reduce import block
    assert {block.scope_of(op[3]) for op in under} <= {None}
    # a trace without the scopes reads as nothing
    other = _fixture("mla_one_chip.json")
    assert dsa.reduce_dsa(other, steps=1) is None


def test_dsa_readers_on_a_traced_run_of_the_fixture(monkeypatch):
    """The eight readers through `for_run`, as the harness calls them."""
    cell = manifest.resolve(ROOT, CELL)
    fx = _fixture()
    table = dsa.reduce_dsa(fx, steps=1)
    monkeypatch.setattr(dsa, "for_run", lambda run: table)
    counted = {"kept_pairs": [62916608], "causal_pairs": [268451840]}
    run = {"cell": cell, "trace": {"steps": 1},
           "device": {"kind": "TPU v5 lite"}, "counters": {"dsa": counted}}
    read = lambda name: manifest.reader(cell, name).read(run)
    by = table["scope_ms_per_step"]
    assert read("dsa_time_pct.tokens") == pytest.approx(table["time_pct"])
    assert read("dsa_index_ms_per_step.tokens") == pytest.approx(
        sum(by[dsa.PROJ].values()) + sum(by[dsa.SCORES].values()))
    assert read("dsa_select_ms_per_step.tokens") == pytest.approx(
        sum(by[dsa.SELECT].values()))
    assert read("dsa_loss_ms_per_step.tokens") == pytest.approx(
        sum(by[dsa.LOSS].values()))
    assert round(read("dsa_kept_pair_pct"), 1) == 23.4
    f = table["flash"]
    kept = dsa.kept_pairs(16384, 2048)
    want = 100 * (f["fwd_calls"] * dsa.attn_fwd_flops(2, 32, kept, 128)
                  + f["bwd_calls"] * dsa.attn_bwd_flops(2, 32, kept, 128)
                  ) / f["seconds"] / 197e12
    assert read("dsa_attn_roofline_pct.tokens") == pytest.approx(want)
    # the kernels' own times on the chip, whole calls: a dense kernel under
    # a mask reads under the kept share of the pairs
    assert 5 < want < 23.5
    # (the fixture keeps a few blocks of a pass, the first and cheapest
    # among them: the two shares are held to the formula, not to a range)
    assert read("dsa_index_roofline_pct.tokens") == pytest.approx(
        dsa.index_roofline_pct(table["kernels"], cell.config, cell.traffic,
                               "TPU v5 lite"))
    assert read("dsa_loss_roofline_pct.tokens") == pytest.approx(
        dsa.loss_roofline_pct(table["kernels"], cell.config, cell.traffic,
                              "TPU v5 lite"))
    # without the scopes (the parent of PR 44, any other model): nothing
    monkeypatch.setattr(dsa, "for_run", lambda run: None)
    run["counters"] = {}
    for name in DSA_METRICS:
        assert read(name) is None, name
    # an end-to-end run has no trace
    monkeypatch.undo()
    assert dsa.for_run({"cell": cell, "trace": None}) is None
