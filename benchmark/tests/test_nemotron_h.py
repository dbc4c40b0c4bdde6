"""CPU tests of what ISSUE 55 added to the benchmark: the
nemotron-twotower-30b-a3b adapter at a toy size against its reference (the
three parts of its check, on the timed step's own call), the cell and its
files, the step's FLOPs by hand, the parent-style failure, and
reduce/nemotron_h.py with its three readers (and the older readers the cell
joins) on a fixture cut from a TPU v5e trace of the cell. No number here is
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
from benchmark.reduce import moe, nemotron_h, ssm      # noqa: E402
from benchmark.tests.test_benchmark import (           # noqa: E402,F401
    _last_line, _shrink, on_cpu, root)

CONFIG = "nemotron-twotower-30b-a3b"
TRAFFIC = "pretrain-seq8192-b1-ep16share"
CELL = f"{CONFIG}.{TRAFFIC}"
NEW_METRICS = {"moe_act_ms_per_step.tokens",
               "ssm_gate_norm_ms_per_step.tokens",
               "moe_rows_per_held_expert"}
JOINED = {
    "compiles_in_window.tokens", "device_idle_pct.tokens",
    "peak_hbm_gib.tokens", "mfu_pct", "fwd_ms_per_step.tokens",
    "recompute_ms_per_step.tokens", "bwd_ms_per_step.tokens",
    "opt_ms_per_step.tokens", "flash_attn_time_pct.tokens",
    "mosaic_time_pct.tokens", "moe_time_pct.tokens",
    "moe_experts_ms_per_step.tokens", "moe_load_max_over_mean",
    "moe_route_dispatch_combine_ms_per_step.tokens", "moe_held_pick_pct",
    "moe_held_experts_roofline_pct.tokens", "ssm_time_pct.tokens",
    "ssm_scan_ms_per_step.tokens", "ssm_conv_gate_ms_per_step.tokens",
    "ssm_scan_roofline_pct.tokens", "setup_import_s", "setup_trace_lower_s",
    "setup_compile_s", "setup_cache_read_s", "setup_cache_miss_programs",
    "setup_programs", "setup_warmup_steps_s"}
# the real structure MEMEM*EME at a width a CPU test can take: 8 heads of 16
# in 4 groups on a state of 16, chunks of 16; attention 4 on 2 at a head of
# 32; 2 of 8 experts held from expert 2 on, top 3, a shared expert
TOY = {"hidden_size": 64, "intermediate_size": 48,
       "moe_intermediate_size": 48,
       "moe_shared_expert_intermediate_size": 96,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
       "mamba_num_heads": 8, "mamba_head_dim": 16, "ssm_state_size": 16,
       "n_groups": 4, "chunk_size": 16, "n_routed_experts": 2,
       "num_routed_experts": 8, "first_expert_held": 2, "num_experts": 2,
       "num_experts_per_tok": 3, "vocab_size": 512,
       "max_position_embeddings": 256}


def _fixture(name="nemotron_h_one_chip.json"):
    with open(os.path.join(HERE, "fixtures", name)) as f:
        return json.load(f)


def _run_toy(root, capsys, seed=2 ** 31 + 55):
    # lr 3e-4: at a toy width dt_bias's gradient is ~1e-9, under AdamW's
    # eps, so at the cell's 3e-6 its update is a fraction of float32's step
    # at |dt_bias| = 4-7 and "the weights it left" would read the rounding
    assumed = manifest.resolve(ROOT, CELL).config["assumed"]
    _shrink(root, CONFIG, TRAFFIC,
            {**TOY, "assumed": {**assumed, "learning_rate": 3e-4}},
            {"sequences": 2, "seq_len": 64, "sync_every": 2,
             "warmup_steps": 3})
    rc = main.main(["--workload", CELL, "--seed", str(seed),
                    "--seconds", "0.5", "--trace", "0"],
                   root=str(root), t0=0.0)
    assert rc == 0
    return _last_line(capsys)


def test_nemotron_h_adapter_runs_and_agrees_with_reference(root, on_cpu,
                                                           capsys):
    adapter = manifest.adapter(manifest.resolve(str(root), CELL))
    line = _run_toy(root, capsys)
    check = line["check"]
    # (A) each of the nine sublayers held to its own number
    assert list(check["hidden_rel_rms_err"]) == [
        f"after_layer_{i}_{kind}" for i, kind in enumerate(
            ("mamba", "mlp", "mamba", "mlp", "mamba", "attention", "mlp",
             "mamba", "mlp"))]
    assert max(check["hidden_rel_rms_err"].values()) < 2e-2
    assert check["loss_abs_err"] < adapter.LOSS_ABS_TOL
    assert set(check["grad_rel_rms_err"]) == set(adapter.GRAD_TOLS) == {
        "lnf_scale", "router", "w_in", "w_out", "wq", "wk", "wv", "wo",
        "shared_w1", "shared_w2", "embed", "head", "expert_w1", "expert_w2",
        "norm", "conv_w", "conv_b", "A_log", "dt_bias", "D", "ssm_norm"}
    assert all(err <= adapter.GRAD_TOLS[n]
               for n, err in check["grad_rel_rms_err"].items()), check
    assert check["bias_entries_unexplained"] == 0
    assert check["dropped_picks"] == 0
    assert 0 < sum(check["held_picks"]) < 4 * 64 * 3
    # (B) every token's picks of four expert layers against float64 scores
    assert check["picks_checked"] == 4 * 64 * 3
    assert check["picks_differ_share"] <= adapter.PICKS_DIFFER_MAX_SHARE
    # (C) the scan's and the grouped norm's float32 parts against float64
    assert check["own_dt_rel_err"] < adapter.OWN_DT_REL_TOL
    assert check["own_log_decay_rel_rms_err"] < adapter.OWN_LOG_DECAY_REL_TOL
    assert check["own_local_state_rel_rms_err"] < 1e-6
    assert check["own_entering_state_rel_rms_err"] < 1e-6
    assert check["own_entering_state_rms"] > 0     # a state IS carried
    assert check["own_gate_norm_rel_rms_err"] < 1e-6
    assert [s["steps"] for s in check["by_sync"]][:2] == [3, 5]
    # the timed step's own call: the step after the window's, its loss, the
    # gradient it applied and the weights it left
    assert check["step"] == line["window"]["steps"] + 3 + 1
    assert set(check["update_rel_err"]) == set(adapter.GRAD_TOLS)
    assert line["correct"], check
    assert line["window"]["compiles"] == 0
    assert line["failed"] == 0
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}


def _state_left_unchanged(monkeypatch):
    """The step returns the weights and moments it was given."""
    from hetu_tpu.models import transformer as tfm
    monkeypatch.setattr(tfm, "adamw_update", lambda params, grads, opt, lr: (
        params, {**opt, "t": opt["t"] + 1.0}))


def _norm_over_all_channels(monkeypatch):
    from hetu_tpu.models import transformer as tfm
    monkeypatch.setattr(
        tfm, "_rms_norm_groups",
        lambda x, scale, groups, eps: tfm._rms_norm32(x, scale, eps))


def _silu_for_relu2(monkeypatch):
    import jax
    from hetu_tpu.models import transformer as tfm
    monkeypatch.setattr(tfm, "_relu2", jax.nn.silu)


def _no_shared_expert(monkeypatch):
    from hetu_tpu.models import transformer as tfm
    routed = tfm._routed_experts
    monkeypatch.setattr(tfm, "_moe_mlp", routed)


@pytest.mark.parametrize("wrong,names", [
    (_state_left_unchanged, ("update_rel_err", "grad_rel_rms_err")),
    # at a toy width the mixers are a hundredth of the stream: the float64
    # statistic of the system's own y silu(z) is what tells
    (_norm_over_all_channels, ("own_gate_norm_rel_rms_err",)),
    (_silu_for_relu2, ("hidden_rel_rms_err",)),
    (_no_shared_expert, ("hidden_rel_rms_err",))],
    ids=lambda x: getattr(x, "__name__", None))
def test_the_nemotron_h_check_holds_the_timed_step(root, on_cpu, capsys,
                                                   monkeypatch, wrong, names):
    """A step wrong on purpose is seen by the check, which compares what the
    job's own compiled step returned: `correct` false, by the named parts."""
    adapter = manifest.adapter(manifest.resolve(str(root), CELL))
    wrong(monkeypatch)
    line = _run_toy(root, capsys)
    check = line["check"]
    assert not line["correct"]
    limits = {"update_rel_err": adapter.UPDATE_REL_ERR_TOL,
              "hidden_rel_rms_err": adapter.HIDDEN_REL_RMS_TOL,
              "own_gate_norm_rel_rms_err": adapter.OWN_GATE_NORM_REL_RMS_TOL,
              "grad_rel_rms_err": 0.9}    # g read off an unmoved m is m
    for name in names:
        got = check[name]
        worst = max(got.values()) if isinstance(got, dict) else got
        assert worst > limits[name], (name, got)


def test_nemotron_h_cell_resolves_with_its_per_layer_metrics():
    cell = manifest.resolve(ROOT, CELL)
    assert cell.chips == 1 and cell.traffic["generator"] == "lm_zipf"
    t = cell.traffic
    assert (t["sequences"], t["seq_len"], t["zipf_exponent"], t["batches"],
            t["sync_every"], t["warmup_steps"], t["trace_steps"],
            t["check_sequences"], t["throughput_metric"]) == (
        1, 8192, 1.1, 8, 10, 15, 5, 1, "tokens_per_s")
    names = {m["name"] for m in cell.per_layer}
    # `<=`: a later PR may add a metric to this cell
    assert NEW_METRICS | JOINED <= names
    # pinned lists (`test_block.py:BLOCK_METRICS`) and readers that find
    # nothing to read here are not joined
    for absent in ("block_mlp_ms_per_step.tokens", "head_ms_per_step.tokens",
                   "step_named_pct.tokens",
                   "ssm_scan_inchunk_ms_per_step.tokens",
                   "moe_shared_ms_per_step.tokens",
                   "moe_experts_roofline_pct.tokens",
                   "flash_attn_roofline_pct.tokens"):
        assert absent not in names, absent
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "setup_s"}
    for m in cell.per_layer:
        assert callable(manifest.reader(cell, m["name"]).read)
    m = manifest.load(ROOT)
    for p in m["per_layer"]:
        assert "workloads" in p, p["name"]
        if p["name"] in NEW_METRICS:
            assert p["workloads"] == [CELL] and p["moves"] == "tokens_per_s"
    # the catalog row's keys, the cut, and nothing else changed
    c = cell.config
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Nemotron-Labs-TwoTower-30B-A3B-Base-BF16")
    assert c["source"] == row["source_url"]
    published = row["config"]
    cut = {"num_hidden_layers": 9, "n_routed_experts": 8,
           "vocab_size": 16384}
    assert {k: c[k] for k in published} == {**published, **cut}
    assert c["hybrid_override_pattern"][:9] == "MEMEM*EME"
    assert len(c["hybrid_override_pattern"]) == 52
    assert (c["num_routed_experts"], c["first_expert_held"],
            c["num_experts"]) == (128, 0, 8)
    # the copies an accepted reader takes a published key by
    assert (c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"],
            c["mamba_n_groups"], c["mamba_chunk_size"]) == (
        c["mamba_num_heads"], c["mamba_head_dim"], c["ssm_state_size"],
        c["n_groups"], c["chunk_size"])
    assert c["layer_types"] == [
        {"M": "mamba", "E": "moe", "*": "attention"}[x]
        for x in "MEMEM*EME"]
    assert list(c["reduced"]) == ["num_hidden_layers", "n_routed_experts",
                                  "vocab_size"]
    for key, said in (("num_hidden_layers", "52"),
                      ("n_routed_experts", "128"),
                      ("vocab_size", "131,072")):
        assert f"published {said}" in c["reduced"][key], key
    assert "16 CHIPS" in c["deployment"]
    # said in so many words: no denoiser tower, no block diffusion
    for text in (c["recipe"], c["deployment"]):
        assert "denoiser tower" in text.lower()
    assert "NOT SUPPORTED" in c["recipe"] and "block-diffusion" in c["recipe"]
    for key in ("gated_norm", "positions", "router", "expert_bias_update",
                "expert_bias_update_rate", "learning_rate",
                "dt_initialisation", "tokens", "reader_keys"):
        assert key in c["assumed"], key
    assert "group_size" in c["assumed"]["gated_norm"]
    assert c["assumed"]["learning_rate"] == 3e-06
    assert c["assumed"]["expert_bias_update_rate"] == 0.03
    entry = next(e for e in m["configs"] if e["name"] == CONFIG)
    assert entry["reduced"] == list(cut) and entry["source"] == c["source"]
    # no other cell reports this configuration's metrics
    for other in ("granite-4.0-h-micro.pretrain-seq8192-b1",
                  "laguna-xs.2.pretrain-seq16384-b1-ep8share"):
        assert not NEW_METRICS & {
            p["name"] for p in manifest.resolve(ROOT, other).per_layer}


def test_nemotron_h_step_flops_by_hand():
    c = manifest.resolve(ROOT, CELL).config
    T, D = 8192, 2688
    by = nemotron_h.forward_flops_by_letter(c, T)
    # M: in-projection, convolution, the scan (its in-chunk products at
    # their causal half: 128 * 129 / 2 pairs a chunk), out-projection
    scan = (64 * (128 * 129 // 2) * 2 * (8 * 128 + 64 * 64)
            + 2 * T * 64 * 2 * 64 * 128) / T
    assert by["M"] == pytest.approx(
        2 * D * (4096 + 6144 + 64) + 2 * 4 * 6144 + scan + 2 * 4096 * D)
    assert by["*"] == 2 * 2 * D * 4096 + 2 * 2 * D * 256 + 2 * T * 4096
    # E: the router, the held picks at the even share 6 * 8 / 128 a token of
    # two matrices each, the shared expert on every token
    assert by["E"] == pytest.approx(
        2 * D * 128 + 6 * 8 / 128 * 4 * D * 1856 + 4 * D * 3712)
    assert by["head"] == 2 * D * 16384
    forward = 4 * by["M"] + by["*"] + 4 * by["E"] + by["head"]
    assert round(forward / 1e6) == 715
    assert round(forward * T / 1e12, 2) == 5.86
    assert [round(100 * x / forward) for x in (
        4 * by["M"], 4 * by["E"], by["*"], by["head"])] == [45, 27, 16, 12]
    assert nemotron_h.flops_per_token(c, T) == pytest.approx(3 * forward)
    # two matrices an expert: the reader of the grouped matmuls' share
    # counts 2 * held * D * F a CALL, whatever the calls a layer
    from benchmark.reduce import lfm2
    assert lfm2.held_expert_matmul_flops(384 * 8, D, 1856) == (
        2 * 384 * 8 * D * 1856)
    # the scan's roofline at THIS shape through the alias keys: bytes bound
    from benchmark.reduce import peaks
    peak = peaks.peaks("TPU v5 lite")
    shape = (1, T, 64, 64, 128, 128, 8)
    least = 4 * max(ssm.ssd_required_flops(*shape) / (peak["tflops"] * 1e12),
                    ssm.ssd_required_bytes(*shape) / (peak["gbs"] * 1e9))
    assert ssm.scan_roofline_pct(10.0, c, {"sequences": 1, "seq_len": T},
                                 "TPU v5 lite") == pytest.approx(
        100 * least / 10e-3)
    assert (ssm.ssd_required_bytes(*shape) / (peak["gbs"] * 1e9)
            > ssm.ssd_required_flops(*shape) / (peak["tflops"] * 1e12))


def test_nemotron_h_rows_per_held_expert_reader():
    cell = manifest.resolve(ROOT, CELL)
    # two traced steps, four expert layers, 128 experts: the eight held take
    # 384 rows each in the first step and 192 in the second
    step = lambda rows: [[rows] * 8 + [5] * 120] * 4
    run = {"cell": cell, "trace": None,
           "counters": {"traced_picks": [step(384), step(192)]}}
    read = manifest.reader(cell, "moe_rows_per_held_expert").read
    assert read(run) == pytest.approx(288.0)
    assert manifest.reader(cell, "moe_held_pick_pct").read(run) == (
        pytest.approx(100 * 288 * 8 / (288 * 8 + 5 * 120)))
    # a program that counts none (the parent of PR 55): nothing, no raise
    assert read({**run, "counters": {}}) is None


def test_nemotron_h_cell_on_a_program_without_the_loader_fails_cleanly(
        root, on_cpu, capsys, monkeypatch):
    """The parent of PR 55 under this PR's benchmark files: `build` raises
    a ManifestError (no loader), the harness exits non-zero in one line, and
    nothing hangs."""
    import hetu_tpu.models
    monkeypatch.setitem(sys.modules, "hetu_tpu.models.hf_nemotron_h", None)
    monkeypatch.delattr(hetu_tpu.models, "hf_nemotron_h", raising=False)
    adapter = manifest.adapter(manifest.resolve(str(root), CELL))
    with pytest.raises(manifest.ManifestError, match="no loader"):
        adapter.build({}, {}, 0, [None], [], None)
    rc = main.main(["--workload", CELL, "--seed", "1", "--seconds", "0.5",
                    "--trace", "0"], root=str(root), t0=0.0)
    assert rc != 0


def _phase(op_name):
    if "transpose(" not in op_name:
        return "fwd"
    return "recompute" if "rematted_computation" in op_name else "bwd"


def test_nemotron_h_table_from_the_fixture():
    """Every expected number is worked out here from the fixture's lines:
    the two scopes are found in forward, recomputed and backward ops, each
    INSIDE the older scope of its part, which the older readers still
    count."""
    fx = _fixture()
    ops = fx["chips"][0]["ops"]
    table = nemotron_h.reduce_scopes(fx, steps=1)
    under = [op for op in ops if nemotron_h.scope_of(op[3])]
    assert under and len(under) < len(ops)
    assert table["device_self_ms_per_step"] == pytest.approx(
        sum(op[2] for op in ops) / 1e6)
    for scope in nemotron_h.SCOPES:
        for p in nemotron_h.PHASES:
            want = sum(op[2] for op in under
                       if nemotron_h.scope_of(op[3]) == scope
                       and _phase(op[3]) == p) / 1e6
            assert want > 0, (scope, p)
            assert table["scope_ms_per_step"][scope][p] == pytest.approx(
                want), (scope, p)
    for op in under:
        outer = {nemotron_h.ACT: "hetu_moe_experts",
                 nemotron_h.GATE_NORM: "hetu_ssm_gate"}[
                     nemotron_h.scope_of(op[3])]
        assert f"/{outer}/{nemotron_h.scope_of(op[3])}/" in op[3]
    # the older readers count the new scopes' time where they counted it
    assert {ssm.scope_of(op[3]) for op in under
            if nemotron_h.scope_of(op[3]) == nemotron_h.GATE_NORM} == {
        "hetu_ssm_gate"}
    assert {moe.scope_of(op[3]) for op in under
            if nemotron_h.scope_of(op[3]) == nemotron_h.ACT} == {
        "hetu_moe_experts"}
    gate = ssm.reduce_ssm(fx, 1)["scope_ms_per_step"]["hetu_ssm_gate"]
    assert sum(gate.values()) >= sum(
        table["scope_ms_per_step"][nemotron_h.GATE_NORM].values())
    text = nemotron_h.render(table)
    assert "hetu_moe_act" in text and "hetu_ssm_gate_norm" in text
    # a trace without the scopes (Granite's: a norm over all channels) reads
    # as nothing
    assert nemotron_h.reduce_scopes(_fixture("ssm_one_chip.json"), 1) is None
    assert "no hetu_moe_act" in nemotron_h.render(None)


def test_nemotron_h_readers_on_a_traced_run_of_the_fixture(monkeypatch):
    """The three new readers and the older ones the cell joins, through
    `for_run`, as the harness calls them: each returns a value."""
    cell = manifest.resolve(ROOT, CELL)
    fx = _fixture()
    tables = {nemotron_h: nemotron_h.reduce_scopes(fx, 1),
              ssm: ssm.reduce_ssm(fx, 1), moe: moe.reduce_moe(fx, 1)}
    for mod, table in tables.items():
        assert table is not None, mod.__name__
        monkeypatch.setattr(mod, "for_run", lambda run, table=table: table)
    step = [[384] * 8 + [0] * 120] * 4
    run = {"cell": cell, "trace": {"steps": 1},
           "device": {"kind": "TPU v5 lite"},
           "counters": {"traced_picks": [step]}}
    read = lambda name: manifest.reader(cell, name).read(run)
    by = tables[nemotron_h]["scope_ms_per_step"]
    assert read("moe_act_ms_per_step.tokens") == pytest.approx(
        sum(by[nemotron_h.ACT].values()))
    assert read("ssm_gate_norm_ms_per_step.tokens") == pytest.approx(
        sum(by[nemotron_h.GATE_NORM].values()))
    assert read("moe_rows_per_held_expert") == 384
    assert read("moe_held_pick_pct") == 100.0
    for name in ("ssm_time_pct.tokens", "ssm_scan_ms_per_step.tokens",
                 "ssm_conv_gate_ms_per_step.tokens",
                 "ssm_scan_roofline_pct.tokens", "moe_time_pct.tokens",
                 "moe_experts_ms_per_step.tokens",
                 "moe_route_dispatch_combine_ms_per_step.tokens",
                 "moe_held_experts_roofline_pct.tokens"):
        value = read(name)
        assert value is not None and value > 0, name
    # the kernels' own times on the chip: shares of a roofline
    assert read("ssm_scan_roofline_pct.tokens") < 100
    assert read("moe_held_experts_roofline_pct.tokens") < 100
    # without the scopes (the parent of PR 55, any other model): nothing
    monkeypatch.setattr(nemotron_h, "for_run", lambda run: None)
    for name in ("moe_act_ms_per_step.tokens",
                 "ssm_gate_norm_ms_per_step.tokens"):
        assert read(name) is None, name
    # an end-to-end run has no trace
    monkeypatch.undo()
    assert nemotron_h.for_run({"cell": cell, "trace": None}) is None
