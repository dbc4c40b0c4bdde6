"""CPU tests of what ISSUE 66 added to the benchmark: the kimi-linear-48b-a3b
adapter at a toy size against its reference (the three parts of its check, on
the timed step's own call), the cell and its files, the step's and the scan's
FLOPs and bytes by hand, the parent-style failure, and reduce/kda.py with its
seven readers (and the older readers the cell joins) on a fixture cut from a
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
from benchmark.reduce import kda, mla, moe, peaks      # noqa: E402
from benchmark.tests.test_benchmark import (           # noqa: E402,F401
    _last_line, _shrink, on_cpu, root)

CONFIG = "kimi-linear-48b-a3b"
TRAFFIC = "pretrain-seq16384-b1-ep32share"
CELL = f"{CONFIG}.{TRAFFIC}"
NEW_METRICS = {"kda_time_pct.tokens", "kda_proj_ms_per_step.tokens",
               "kda_conv_gate_ms_per_step.tokens",
               "kda_scan_ms_per_step.tokens", "kda_solve_ms_per_step.tokens",
               "kda_chunk_log_decay_min", "kda_scan_roofline_pct.tokens"}
JOINED = {
    "compiles_in_window.tokens", "device_idle_pct.tokens",
    "peak_hbm_gib.tokens", "mfu_pct", "fwd_ms_per_step.tokens",
    "recompute_ms_per_step.tokens", "bwd_ms_per_step.tokens",
    "opt_ms_per_step.tokens", "flash_attn_time_pct.tokens",
    "mosaic_time_pct.tokens", "moe_time_pct.tokens",
    "moe_experts_ms_per_step.tokens", "moe_load_max_over_mean",
    "moe_route_dispatch_combine_ms_per_step.tokens", "moe_held_pick_pct",
    "moe_held_experts_roofline_pct.tokens",
    "mla_time_pct.tokens", "mla_proj_ms_per_step.tokens",
    "mla_kv_up_ms_per_step.tokens", "mla_attn_roofline_pct.tokens",
    "setup_import_s", "setup_trace_lower_s", "setup_compile_s",
    "setup_cache_read_s", "setup_cache_miss_programs", "setup_programs",
    "setup_warmup_steps_s"}
# the real structure (KDA + dense, KDA, KDA, latent, KDA) at a width a CPU
# test can take: 4 heads of 16, 2 of 8 experts held from expert 2 on, top 2
TOY = {"hidden_size": 64, "intermediate_size": 128,
       "moe_intermediate_size": 48, "num_attention_heads": 4,
       "num_key_value_heads": 4, "head_dim": 16, "kv_lora_rank": 32,
       "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 24,
       "linear_attn_config": {
           "full_attn_layers": [4, 8], "head_dim": 16,
           "kda_layers": [1, 2, 3, 5, 6, 7], "num_heads": 4,
           "short_conv_kernel_size": 4},
       "num_experts": 2, "num_routed_experts": 8, "first_expert_held": 2,
       "num_experts_per_token": 2, "vocab_size": 512,
       "model_max_length": 256}


def _fixture(name="kda_one_chip.json"):
    with open(os.path.join(HERE, "fixtures", name)) as f:
        return json.load(f)


def _run_toy(root, capsys, seed=2 ** 31 + 66):
    # lr 3e-4: at a toy width the decay's gradients are under AdamW's eps, so
    # at the cell's 3e-6 "the weights it left" would read float32's rounding
    assumed = manifest.resolve(ROOT, CELL).config["assumed"]
    _shrink(root, CONFIG, TRAFFIC,
            {**TOY, "assumed": {**assumed, "learning_rate": 3e-4}},
            {"sequences": 2, "seq_len": 128, "sync_every": 2,
             "warmup_steps": 3})
    rc = main.main(["--workload", CELL, "--seed", str(seed),
                    "--seconds", "0.5", "--trace", "0"],
                   root=str(root), t0=0.0)
    assert rc == 0
    return _last_line(capsys)


def test_kimi_linear_adapter_runs_and_agrees_with_reference(root, on_cpu,
                                                            capsys):
    adapter = manifest.adapter(manifest.resolve(str(root), CELL))
    line = _run_toy(root, capsys)
    check = line["check"]
    # (A) each of the four runs of one kind held to its own number
    assert list(check["hidden_rel_rms_err"]) == [
        "after_layer_0_kda", "after_layer_2_kda", "after_layer_3_mla",
        "after_layer_4_kda"]
    assert max(check["hidden_rel_rms_err"].values()) < 2e-2
    assert check["loss_abs_err"] < adapter.LOSS_ABS_TOL
    assert set(check["grad_rel_rms_err"]) == set(adapter.GRAD_TOLS) == {
        "lnf_scale", "matrix", "expert", "router", "vector", "kda_decay",
        "kda_beta"}
    assert check["bias_entries_unexplained"] == 0
    assert check["dropped_picks"] == 0
    assert 0 < sum(check["held_picks"]) < 4 * 128 * 2
    # (B) every token's picks of four expert layers against float64 scores
    assert check["picks_checked"] == 4 * 128 * 2
    assert check["picks_differ_share"] <= adapter.PICKS_DIFFER_MAX_SHARE
    # (C) the scan's and the head norm's float32 parts against float64
    assert check["own_log_decay_rel_rms_err"] < adapter.OWN_LOG_DECAY_REL_TOL
    assert check["own_u_rel_rms_err"] < 1e-5
    assert check["own_entering_state_rel_rms_err"] < 1e-5
    assert check["own_entering_state_rms"] > 0     # a state IS carried
    assert check["own_out_rel_rms_err"] < 1e-5
    assert check["own_head_norm_rel_rms_err"] < 1e-6
    assert check["chunk_log_decay_min"] < 0
    # the timed step's own call: the step after the window's
    assert check["step"] == line["window"]["steps"] + 3 + 1
    assert set(check["update_rel_err"]) == set(adapter.GRAD_TOLS)
    assert check["failed_parts"] == [] and line["correct"], check
    assert line["window"]["compiles"] == 0
    assert line["failed"] == 0
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}


def _state_left_unchanged(monkeypatch):
    """The step returns the weights and moments it was given."""
    from hetu_tpu.models import transformer as tfm
    monkeypatch.setattr(tfm, "adamw_update", lambda params, grads, opt, lr: (
        params, {**opt, "t": opt["t"] + 1.0}))


def _state_in_bfloat16(monkeypatch):
    """The carried state rounded to bfloat16 as each chunk leaves it."""
    import jax
    from hetu_tpu.models import kda as kda_model
    step = kda_model._chunk_step

    def rounded(S, parts):
        S, out = step(S, parts)
        return jax.lax.reduce_precision(S, 8, 7), out

    monkeypatch.setattr(kda_model, "_chunk_step", rounded)


def _gate_before_norm(monkeypatch):
    import jax
    import jax.numpy as jnp
    from hetu_tpu.models import transformer as tfm

    def gate_first(o, gate, scale, eps):
        o = o * gate.reshape(o.shape)
        return (o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
                * scale).reshape(gate.shape)

    monkeypatch.setattr(tfm, "_kda_gate_norm", gate_first)


def _key_rotated(monkeypatch):
    """The latent layer's q and shared key rotated, as kanana's are."""
    import dataclasses
    from hetu_tpu.models import hf_kimi_linear
    build = hf_kimi_linear.config_from_hf

    def rotating(c, **kw):
        cfg = build(c, **kw)
        return dataclasses.replace(cfg, mla=dataclasses.replace(
            cfg.mla, rotate=True))

    monkeypatch.setattr(hf_kimi_linear, "config_from_hf", rotating)


@pytest.mark.parametrize("wrong,parts", [
    (_state_left_unchanged, ("update",)),
    (_state_in_bfloat16, ("own_state",)),
    (_gate_before_norm, ("hidden", "own_head_norm")),
    # a rotation of q and the shared key: at 128 positions the stream hardly
    # moves, W_q's and W_kv_a's gradients do
    (_key_rotated, ("grads_matrix",))],
    ids=lambda x: getattr(x, "__name__", None))
def test_the_kimi_linear_check_holds_the_timed_step(root, on_cpu, capsys,
                                                    monkeypatch, wrong, parts):
    """A step wrong on purpose is seen by the check, which compares what the
    job's own compiled step returned: `correct` false, by the named parts."""
    wrong(monkeypatch)
    line = _run_toy(root, capsys)
    assert not line["correct"]
    assert set(parts) <= set(line["check"]["failed_parts"]), line["check"][
        "failed_parts"]


def test_kimi_linear_cell_resolves_with_its_per_layer_metrics():
    cell = manifest.resolve(ROOT, CELL)
    assert cell.chips == 1 and cell.traffic["generator"] == "lm_zipf"
    t = cell.traffic
    assert (t["sequences"], t["seq_len"], t["zipf_exponent"], t["batches"],
            t["sync_every"], t["warmup_steps"], t["trace_steps"],
            t["check_sequences"], t["throughput_metric"]) == (
        1, 16384, 1.1, 8, 10, 15, 5, 1, "tokens_per_s")
    names = {m["name"] for m in cell.per_layer}
    # `<=`: a later PR may add a metric to this cell
    assert NEW_METRICS | JOINED <= names
    # pinned lists and readers that find nothing to read here are not joined
    for absent in ("block_mlp_ms_per_step.tokens", "head_ms_per_step.tokens",
                   "step_named_pct.tokens",
                   "ssm_scan_inchunk_ms_per_step.tokens",
                   "ssm_scan_ms_per_step.tokens",
                   "moe_rows_per_held_expert",    # test_nemotron_h.py's pin
                   "moe_shared_ms_per_step.tokens",
                   "moe_experts_roofline_pct.tokens",
                   "flash_attn_roofline_pct.tokens"):
        assert absent not in names, absent
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "setup_s"}
    for m in cell.per_layer:
        assert callable(manifest.reader(cell, m["name"]).read)
    m = manifest.load(ROOT)
    for p in m["per_layer"]:
        if p["name"] in NEW_METRICS:
            assert p["workloads"] == [CELL] and p["moves"] == "tokens_per_s"
            assert p["layer"] == ("kernels" if "roofline" in p["name"]
                                  else "flagship step")
    # the catalog row's keys, the three cuts, and nothing else changed
    c = cell.config
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    assert c["source"] == row["source_url"]
    published = row["config"]
    cut = {"num_hidden_layers": 5, "num_experts": 8, "vocab_size": 20480}
    assert {k: c[k] for k in published} == {**published, **cut}
    assert (c["num_routed_experts"], c["first_expert_held"]) == (256, 0)
    assert kda.mixers_of(c) == ["kda", "kda", "kda", "mla", "kda"]
    assert list(c["reduced"]) == list(cut)
    for key, said in (("num_hidden_layers", "27"), ("num_experts", "256"),
                      ("vocab_size", "163,840")):
        assert f"published {said}" in c["reduced"][key], key
    assert "32 CHIPS" in c["deployment"]
    for key in ("mla_nope_columns", "kda_silu_in_convolution",
                "kda_l2_norm_eps", "kda_initial_values", "parameter_names",
                "expert_bias_update", "expert_bias_update_rate",
                "learning_rate", "optimizer", "adamw", "kda_chunk"):
        assert key in c["assumed"], key
    assert c["assumed"]["learning_rate"] == 3e-06
    assert c["assumed"]["expert_bias_update_rate"] == 0.03
    entry = next(e for e in m["configs"] if e["name"] == CONFIG)
    assert entry["reduced"] == list(cut) and entry["source"] == c["source"]
    # the manifest's form: a `why` is one printable line of 1 to 200
    # characters (the first hand-in of this cell was refused for 204)
    work = next(w for w in m["workloads"] if w["name"] == CELL)
    for why in (entry["why"], work["why"]):
        assert 1 <= len(why) <= 200 and why.isprintable(), len(why)
    # no other cell reports this configuration's metrics
    for other in ("kanana-2-30b-a3b.pretrain-seq8192-ep8share",
                  "granite-4.0-h-micro.pretrain-seq8192-b1"):
        assert not NEW_METRICS & {
            p["name"] for p in manifest.resolve(ROOT, other).per_layer}


def test_kimi_linear_step_and_scan_flops_and_bytes_by_hand():
    c = manifest.resolve(ROOT, CELL).config
    T, D, H, K = 16384, 2304, 32, 128
    # the scan, a head and chunk of 64: two pairwise products at their causal
    # half (64 * 65 / 2 pairs of 128 columns), the triangular system's 64 *
    # 63 / 2 entries against 256 right-hand columns, P U at its causal half;
    # a position: three products with the 128 x 128 state
    a_chunk = (2 * 2080 * 2 * 128 + 2016 * 2 * 256 + 2080 * 2 * 128)
    a_position = 3 * 2 * 128 * 128
    forward = H * (256 * a_chunk + T * a_position)
    assert kda.scan_required_flops(1, T, H, K, K, 64) == 3 * forward
    assert round(forward / 1e9, 1) == 73.1        # ~0.3 TFLOP over 4 layers
    # bytes: q, k, v, o at bfloat16, g float32 a channel, beta a head
    assert kda.scan_required_bytes(1, T, H, K, K) == 3 * T * H * (
        2 * 4 * 128 + 4 * 128 + 4)
    peak = peaks.peaks("TPU v5 lite")
    by_flops = 3 * forward / (peak["tflops"] * 1e12)
    by_bytes = kda.scan_required_bytes(1, T, H, K, K) / (peak["gbs"] * 1e9)
    assert by_bytes > by_flops          # the bytes bound: 2.96 ms a layer
    assert kda.scan_roofline_pct(100.0, c, {"sequences": 1, "seq_len": T},
                                 "TPU v5 lite") == pytest.approx(
        100 * 4 * by_bytes / 0.1)
    by = kda.forward_flops_by_part(c, T)
    assert by["kda"] == pytest.approx(
        2 * D * 3 * 4096 + 2 * (2 * D * 128 + 2 * 128 * 4096) + 2 * D * 32
        + 2 * 4 * 3 * 4096 + forward / T + 2 * 4096 * D)
    assert by["mla"] == (2 * D * 32 * 192 + 2 * D * 576 + 2 * 512 * 32 * 256
                         + T * 32 * 320 + 2 * 32 * 128 * D)
    assert by["dense"] == 6 * D * 9216
    # the router, the held picks at the even share 8 * 8 / 256 a token, the
    # shared expert on every token
    assert by["experts"] == pytest.approx(
        2 * D * 256 + 8 * 8 / 256 * 6 * D * 1024 + 6 * D * 1024)
    assert by["head"] == 2 * D * 20480
    total = (4 * by["kda"] + by["mla"] + by["dense"] + 4 * by["experts"]
             + by["head"])
    assert kda.flops_per_token(c, T) == pytest.approx(3 * total)
    # the ISSUE's arithmetic: ~14 TFLOP forward a sequence, the KDA mixers
    # near 40 % of it by required operations
    assert 13.5 < total * T / 1e12 < 15.0
    assert 0.35 < 4 * by["kda"] / total < 0.45
    # the joined readers at this cell's shape: ONE latent layer of five,
    # heads of 192 / 128 at 16,384 keys
    flash = {"fwd_calls": 2, "bwd_calls": 1, "seconds": 50e-3}
    P = T * (T + 1) // 2
    want = (2 * 2 * 32 * P * 320 + 2 * 32 * P * (3 * 192 + 2 * 128))
    got = mla.attn_roofline_pct(flash, c, {"sequences": 1, "seq_len": T},
                                "TPU v5 lite")
    assert got == pytest.approx(100 * want / 50e-3 / 197e12, rel=1e-6)


def test_kimi_linear_counter_readers():
    cell = manifest.resolve(ROOT, CELL)
    step = lambda rows: [[rows] * 8 + [5] * 248] * 4
    run = {"cell": cell, "trace": None,
           "counters": {"traced_picks": [step(512), step(256)],
                        "kda": {"chunk_log_decay_min": -101.5}}}
    read = lambda name, r=run: manifest.reader(cell, name).read(r)
    assert read("kda_chunk_log_decay_min") == -101.5
    assert read("moe_held_pick_pct") == pytest.approx(
        100 * 384 * 8 / (384 * 8 + 5 * 248))
    # a program that counts none (the parent of PR 66): nothing, no raise
    assert read("kda_chunk_log_decay_min", {**run, "counters": {}}) is None
    # an end-to-end run has no trace: the trace readers return nothing
    for name in sorted(NEW_METRICS - {"kda_chunk_log_decay_min"}):
        assert read(name) is None, name


def test_kimi_linear_cell_on_a_program_without_the_loader_fails_cleanly(
        root, on_cpu, capsys, monkeypatch):
    """The parent of PR 66 under this PR's benchmark files: `build` raises
    a ManifestError (no loader), the harness exits non-zero in one line, and
    nothing hangs."""
    import hetu_tpu.models
    monkeypatch.setitem(sys.modules, "hetu_tpu.models.hf_kimi_linear", None)
    monkeypatch.delattr(hetu_tpu.models, "hf_kimi_linear", raising=False)
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


def test_kda_table_from_the_fixture():
    """Every expected number is worked out here from the fixture's lines:
    the five scopes are found in forward, recomputed and backward ops, an op
    under its INNERMOST one (the solve inside the scan is the solve's)."""
    fx = _fixture()
    ops = fx["chips"][0]["ops"]
    table = kda.reduce_scopes(fx, steps=1)
    under = [op for op in ops if kda.scope_of(op[3])]
    assert under and len(under) < len(ops)
    assert table["device_self_ms_per_step"] == pytest.approx(
        sum(op[2] for op in ops) / 1e6)
    for scope in kda.SCOPES:
        for p in kda.PHASES:
            want = sum(op[2] for op in under if kda.scope_of(op[3]) == scope
                       and _phase(op[3]) == p) / 1e6
            assert want > 0, (scope, p)
            assert table["scope_ms_per_step"][scope][p] == pytest.approx(
                want), (scope, p)
    for op in under:
        if kda.scope_of(op[3]) == kda.SOLVE:
            assert f"{kda.SCAN}/" in op[3]
        # of their own: inside none of the attention block's scopes
        assert "hetu_blk_qkv" not in op[3] and "hetu_blk_attn" not in op[3]
    text = kda.render(table)
    assert all(s in text for s in kda.SCOPES)
    # a trace without the scopes (kanana's) reads as nothing
    assert kda.reduce_scopes(_fixture("mla_one_chip.json"), 1) is None
    assert "no hetu_kda_" in kda.render(None)


def test_kda_readers_on_a_traced_run_of_the_fixture(monkeypatch):
    """The six trace readers and the older ones the cell joins, through
    `for_run`, as the harness calls them: each returns a value."""
    cell = manifest.resolve(ROOT, CELL)
    fx = _fixture()
    tables = {kda: kda.reduce_scopes(fx, 1), mla: mla.reduce_mla(fx, 1),
              moe: moe.reduce_moe(fx, 1)}
    for mod, table in tables.items():
        assert table is not None, mod.__name__
        monkeypatch.setattr(mod, "for_run", lambda run, table=table: table)
    step = [[512] * 8 + [0] * 248] * 4
    run = {"cell": cell, "trace": {"steps": 1},
           "device": {"kind": "TPU v5 lite"},
           "counters": {"traced_picks": [step],
                        "kda": {"chunk_log_decay_min": -99.0}}}
    read = lambda name: manifest.reader(cell, name).read(run)
    by = tables[kda]["scope_ms_per_step"]
    total = lambda *scopes: sum(sum(by[s].values()) for s in scopes)
    assert read("kda_proj_ms_per_step.tokens") == pytest.approx(
        total(kda.PROJ))
    assert read("kda_conv_gate_ms_per_step.tokens") == pytest.approx(
        total(kda.CONV, kda.GATE))
    assert read("kda_scan_ms_per_step.tokens") == pytest.approx(
        total(kda.SCAN, kda.SOLVE))
    assert read("kda_solve_ms_per_step.tokens") == pytest.approx(
        total(kda.SOLVE))
    assert read("kda_time_pct.tokens") == pytest.approx(
        100 * total(*kda.SCOPES) / tables[kda]["device_self_ms_per_step"])
    assert 0 < read("kda_scan_roofline_pct.tokens") == pytest.approx(
        kda.scan_roofline_pct(total(kda.SCAN, kda.SOLVE), cell.config,
                              cell.traffic, "TPU v5 lite"))
    assert read("kda_chunk_log_decay_min") == -99.0
    for name in ("mla_time_pct.tokens", "mla_proj_ms_per_step.tokens",
                 "mla_kv_up_ms_per_step.tokens",
                 "mla_attn_roofline_pct.tokens", "moe_time_pct.tokens",
                 "moe_experts_ms_per_step.tokens",
                 "moe_route_dispatch_combine_ms_per_step.tokens",
                 "moe_held_experts_roofline_pct.tokens"):
        value = read(name)
        assert value is not None and value > 0, name
    assert read("mla_attn_roofline_pct.tokens") < 100
    # without the scopes (the parent of PR 66, any other model): nothing
    monkeypatch.setattr(kda, "for_run", lambda run: None)
    for name in sorted(NEW_METRICS - {"kda_chunk_log_decay_min"}):
        assert read(name) is None, name
    monkeypatch.undo()
    assert kda.for_run({"cell": cell, "trace": None}) is None
