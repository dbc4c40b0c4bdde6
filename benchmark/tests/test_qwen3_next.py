"""CPU tests of what ISSUE 68 added to the benchmark: the qwen3-next-80b-a3b
adapter at a toy size against its reference (the three parts of its check, on
the timed step's own call), the cell and its files, the step's and the scan's
FLOPs and bytes by hand, the parent-style failure, and reduce/gdn.py with its
six readers (and the older readers the cell joins) on a fixture cut from a
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
from benchmark.reduce import gdn, moe, peaks, swa      # noqa: E402
from benchmark.tests.test_benchmark import (           # noqa: E402,F401
    _last_line, _shrink, on_cpu, root)

CONFIG = "qwen3-next-80b-a3b"
TRAFFIC = "pretrain-seq16384-b1-ep16share"
CELL = f"{CONFIG}.{TRAFFIC}"
NEW_METRICS = {"gdn_time_pct.tokens", "gdn_proj_ms_per_step.tokens",
               "gdn_conv_gate_ms_per_step.tokens",
               "gdn_scan_ms_per_step.tokens", "gdn_chunk_log_decay_min",
               "gdn_scan_roofline_pct.tokens"}
JOINED = {
    "compiles_in_window.tokens", "device_idle_pct.tokens",
    "peak_hbm_gib.tokens", "mfu_pct", "fwd_ms_per_step.tokens",
    "recompute_ms_per_step.tokens", "bwd_ms_per_step.tokens",
    "opt_ms_per_step.tokens", "flash_attn_time_pct.tokens",
    "mosaic_time_pct.tokens", "moe_time_pct.tokens",
    "moe_experts_ms_per_step.tokens", "moe_load_max_over_mean",
    "moe_route_dispatch_combine_ms_per_step.tokens", "moe_held_pick_pct",
    "moe_held_experts_roofline_pct.tokens", "attn_gate_ms_per_step.tokens",
    "attn_rope_ms_per_step.tokens", "setup_import_s", "setup_trace_lower_s", "setup_compile_s",
    "setup_cache_read_s", "setup_cache_miss_programs", "setup_programs",
    "setup_warmup_steps_s"}
# the real structure (GDN, GDN, GDN, gated attention) at a width a CPU test
# can take: 2 key heads under 4 value heads of 16, 4 query heads of 32 on 2,
# 2 of 8 experts held from expert 2 on, top 3
TOY = {"hidden_size": 64, "intermediate_size": 128,
       "moe_intermediate_size": 48, "shared_expert_intermediate_size": 40,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
       "linear_num_key_heads": 2, "linear_num_value_heads": 4,
       "linear_key_head_dim": 16, "linear_value_head_dim": 16,
       "num_experts": 2, "num_routed_experts": 8, "first_expert_held": 2,
       "num_experts_per_tok": 3, "vocab_size": 512,
       "max_position_embeddings": 256}


def _fixture(name="gdn_one_chip.json"):
    with open(os.path.join(HERE, "fixtures", name)) as f:
        return json.load(f)


def _run_toy(root, capsys, monkeypatch, seed=2 ** 31 + 68):
    # the decays made to MATTER: at the published A = U(0, 16) a head of a
    # toy model forgets everything a position and its decay's gradient is
    # rounding (the cell's limits are the chip's readings at 16,384 tokens);
    # A_log - 3 is A in (0, 0.8]
    import dataclasses
    from hetu_tpu.models import transformer as tfm
    kind = tfm._KINDS["gdn"]

    def slower(ks, cfg, n):
        leaves = kind.init(ks, cfg, n)
        return {**leaves, "gdn_A_log": leaves["gdn_A_log"] - 3.0}

    monkeypatch.setitem(tfm._KINDS, "gdn",
                        dataclasses.replace(kind, init=slower))
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


def test_qwen3_next_adapter_runs_and_agrees_with_reference(root, on_cpu,
                                                           capsys,
                                                           monkeypatch):
    adapter = manifest.adapter(manifest.resolve(str(root), CELL))
    line = _run_toy(root, capsys, monkeypatch)
    check = line["check"]
    # (A) each of the two runs of one kind held to its own number
    assert list(check["hidden_rel_rms_err"]) == [
        "after_layer_2_gdn", "after_layer_3_attention"]
    assert max(check["hidden_rel_rms_err"].values()) < 2e-2
    assert check["loss_abs_err"] < adapter.LOSS_ABS_TOL
    assert set(check["grad_rel_rms_err"]) == set(adapter.GRAD_TOLS) == {
        "lnf_scale", "matrix", "expert", "router", "vector", "norm_w",
        "gdn_decay", "gdn_ba", "shared_gate"}
    assert check["dropped_picks"] == 0
    assert 0 < sum(check["held_picks"]) < 4 * 128 * 3
    assert len(check["held_pick_pct_by_layer"]) == 4
    # (B) every token's picks of four expert layers against float64 scores
    assert check["picks_checked"] == 4 * 128 * 3
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


# ONE system-side variant here (a toy run is ~35 s of tier-1's budget); the
# carried state in bfloat16, SiLU(z) before the head norm, attention's gate a
# head and the reference-side table were read on the chip (PERF.md section 6)
# and, at a toy size, in tests/test_qwen3_next_model.py
@pytest.mark.parametrize("wrong,parts", [
    (_state_left_unchanged, ("update",))],
    ids=lambda x: getattr(x, "__name__", None))
def test_the_qwen3_next_check_holds_the_timed_step(root, on_cpu, capsys,
                                                   monkeypatch, wrong, parts):
    """A step wrong on purpose is seen by the check, which compares what the
    job's own compiled step returned: `correct` false, by the named parts."""
    wrong(monkeypatch)
    line = _run_toy(root, capsys, monkeypatch)
    assert not line["correct"]
    assert set(parts) <= set(line["check"]["failed_parts"]), line["check"][
        "failed_parts"]


def test_qwen3_next_cell_resolves_with_its_per_layer_metrics():
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
                   "step_named_pct.tokens", "moe_act_ms_per_step.tokens",
                   "moe_rows_per_held_expert",
                   "moe_shared_ms_per_step.tokens",
                   "moe_experts_roofline_pct.tokens",
                   "flash_attn_roofline_pct.tokens",
                   "full_attn_roofline_pct.tokens",   # reads laguna's keys
                   "kda_scan_ms_per_step.tokens", "kda_time_pct.tokens",
                   "ssm_scan_ms_per_step.tokens", "mla_time_pct.tokens"):
        assert absent not in names, absent
    assert not any(n.startswith("gdn_solve") for n in names)
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
                   if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    assert c["source"] == row["source_url"]
    published = row["config"]
    cut = {"num_hidden_layers": 4, "num_experts": 32, "vocab_size": 19072}
    assert {k: c[k] for k in published} == {**published, **cut}
    assert (c["num_routed_experts"], c["first_expert_held"]) == (512, 0)
    assert gdn.mixers_of(c) == ["gdn", "gdn", "gdn", "attention"]
    assert list(c["reduced"]) == list(cut)
    for key, said in (("num_hidden_layers", "48"), ("num_experts", "512"),
                      ("vocab_size", "151,936")):
        assert f"published {said}" in c["reduced"][key], key
    assert c["vocab_size"] * 8 == 149 * 128 * 8 == 152576
    assert "16 CHIPS" in c["deployment"]
    for key in ("mtp", "gdn_initial_values", "gdn_l2_norm_eps",
                "router_aux_loss_coef", "router_aux_loss", "weights",
                "compute_dtype", "learning_rate", "optimizer", "adamw",
                "gdn_chunk"):
        assert key in c["assumed"], key
    assert c["assumed"]["learning_rate"] == 3e-06
    assert c["assumed"]["router_aux_loss_coef"] == 0.001
    entry = next(e for e in m["configs"] if e["name"] == CONFIG)
    assert entry["reduced"] == list(cut) and entry["source"] == c["source"]
    # the manifest's form: a `why` is one printable line of 1 to 200
    # characters
    work = next(w for w in m["workloads"] if w["name"] == CELL)
    for why in (entry["why"], work["why"]):
        assert 1 <= len(why) <= 200 and why.isprintable(), len(why)
    # no other cell reports this configuration's metrics
    for other in ("kimi-linear-48b-a3b.pretrain-seq16384-b1-ep32share",
                  "granite-4.0-h-micro.pretrain-seq8192-b1"):
        assert not NEW_METRICS & {
            p["name"] for p in manifest.resolve(ROOT, other).per_layer}


def test_qwen3_next_step_and_scan_flops_and_bytes_by_hand():
    c = manifest.resolve(ROOT, CELL).config
    T, D, Hk, Hv, K = 16384, 2048, 16, 32, 128
    # the scan, a chunk of 64: a KEY head's two products of positions at
    # their causal half (64 * 65 / 2 pairs of 128 columns), shared by its two
    # value heads; a VALUE head's triangular system (64 * 63 / 2 entries
    # against 256 right-hand columns) and P U at its causal half; a position:
    # a value head's three products with the 128 x 128 state
    a_chunk = (Hk * 2 * 2080 * 2 * 128
               + Hv * (2016 * 2 * 256 + 2080 * 2 * 128))
    a_position = Hv * 3 * 2 * 128 * 128
    forward = 256 * a_chunk + T * a_position
    assert gdn.scan_required_flops(1, T, Hk, Hv, K, K, 64) == 3 * forward
    assert round(forward / 1e9, 1) == 68.7        # ~0.2 TFLOP over 3 layers
    # bytes: q, k a KEY head and v, o a value head at bfloat16; g and beta
    # float32, ONE each a value head (kimi's g is 128 a head)
    assert gdn.scan_required_bytes(1, T, Hk, Hv, K, K) == 3 * T * (
        2 * (2 * 16 * 128 + 2 * 32 * 128) + 8 * 32)
    peak = peaks.peaks("TPU v5 lite")
    by_flops = 3 * forward / (peak["tflops"] * 1e12)
    by_bytes = gdn.scan_required_bytes(1, T, Hk, Hv, K, K) / (
        peak["gbs"] * 1e9)
    assert by_bytes > by_flops          # the bytes bound: 1.49 ms a layer
    assert 1.4e-3 < by_bytes < 1.6e-3
    assert gdn.scan_roofline_pct(100.0, c, {"sequences": 1, "seq_len": T},
                                 "TPU v5 lite") == pytest.approx(
        100 * 3 * by_bytes / 0.1)
    # it cannot read over 100: the least time over a time that holds it
    assert gdn.scan_roofline_pct(3 * by_bytes * 1e3, c,
                                 {"sequences": 1, "seq_len": T},
                                 "TPU v5 lite") == pytest.approx(100.0)
    by = gdn.forward_flops_by_part(c, T)
    assert by["gdn"] == pytest.approx(
        2 * D * 12288 + 2 * D * 64 + 2 * 4 * 8192 + forward / T
        + 2 * 4096 * D)
    assert by["attention"] == (2 * D * 2 * 16 * 256 + 2 * D * 2 * 2 * 256
                               + T * 16 * 512 + 2 * 16 * 256 * D)
    # the router, the held picks at the even share 10 * 32 / 512 a token, the
    # shared expert and its gate on every token
    assert by["experts"] == pytest.approx(
        2 * D * 512 + 10 * 32 / 512 * 6 * D * 512 + 6 * D * 512 + 2 * D)
    assert by["head"] == 2 * D * 19072
    total = 3 * by["gdn"] + by["attention"] + 4 * by["experts"] + by["head"]
    assert gdn.flops_per_token(c, T) == pytest.approx(3 * total)
    # the ISSUE's arithmetic: matmuls 6.29 TFLOP + the causal core 2.20 + the
    # scans ~0.2 forward a sequence; the GDN mixers the largest part, the
    # attention layer about a third by required operations
    assert 8.4 < total * T / 1e12 < 9.0
    assert 0.38 < 3 * by["gdn"] / total < 0.43
    assert 0.33 < by["attention"] / total < 0.38
    assert T * T * 16 * 512 / 1e12 == pytest.approx(2.199, abs=1e-3)


def test_qwen3_next_counter_readers():
    cell = manifest.resolve(ROOT, CELL)
    step = lambda rows: [[rows] * 32 + [5] * 480] * 4
    run = {"cell": cell, "trace": None,
           "counters": {"traced_picks": [step(320), step(160)],
                        "gdn": {"chunk_log_decay_min": -1301.5}}}
    read = lambda name, r=run: manifest.reader(cell, name).read(r)
    assert read("gdn_chunk_log_decay_min") == -1301.5
    assert read("moe_held_pick_pct") == pytest.approx(
        100 * 240 * 32 / (240 * 32 + 5 * 480))
    # a program that counts none (the parent of PR 68): nothing, no raise
    assert read("gdn_chunk_log_decay_min", {**run, "counters": {}}) is None
    # an end-to-end run has no trace: the trace readers return nothing
    for name in sorted(NEW_METRICS - {"gdn_chunk_log_decay_min"}):
        assert read(name) is None, name


def test_qwen3_next_cell_on_a_program_without_the_loader_fails_cleanly(
        root, on_cpu, capsys, monkeypatch):
    """The parent of PR 68 under this PR's benchmark files: `build` raises
    a ManifestError (no loader), the harness exits non-zero in one line, and
    nothing hangs."""
    import hetu_tpu.models
    monkeypatch.setitem(sys.modules, "hetu_tpu.models.hf_qwen3_next", None)
    monkeypatch.delattr(hetu_tpu.models, "hf_qwen3_next", raising=False)
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


def test_gdn_table_from_the_fixture():
    """Every expected number is worked out here from the fixture's lines: the
    scopes are found in forward, recomputed and backward ops, an op under its
    INNERMOST one; the Mosaic kernels the cell runs (`kda_fwd`, `kda_bwd`)
    sit under `hetu_gdn_scan` with their own `hetu_kda_scan` inside it, in
    all three phases (the solve is inside the kernels)."""
    fx = _fixture()
    ops = fx["chips"][0]["ops"]
    table = gdn.reduce_scopes(fx, steps=1)
    under = [op for op in ops if gdn.scope_of(op[3])]
    assert under and len(under) < len(ops)
    assert table["device_self_ms_per_step"] == pytest.approx(
        sum(op[2] for op in ops) / 1e6)
    for scope in gdn.SCOPES:
        for p in gdn.PHASES:
            want = sum(op[2] for op in under if gdn.scope_of(op[3]) == scope
                       and _phase(op[3]) == p) / 1e6
            assert want > 0, (scope, p)
            assert table["scope_ms_per_step"][scope][p] == pytest.approx(
                want), (scope, p)
    kernels = [op for op in ops if "/kda_fwd/" in op[3]
               or "/kda_bwd/" in op[3]]
    assert {_phase(op[3]) for op in kernels} == set(gdn.PHASES)
    for op in kernels:
        assert f"{gdn.SCAN}/hetu_kda_scan/" in op[3]
        assert gdn.scope_of(op[3]) == gdn.SCAN
    for op in under:
        # of their own: inside none of the attention block's scopes
        assert "hetu_blk_qkv" not in op[3] and "hetu_blk_attn" not in op[3]
    text = gdn.render(table)
    assert all(s in text for s in gdn.SCOPES)
    # a trace without the scopes (kimi's, whose scan is `hetu_kda_scan`
    # alone; kanana's) reads as nothing
    assert gdn.reduce_scopes(_fixture("kda_one_chip.json"), 1) is None
    assert gdn.reduce_scopes(_fixture("mla_one_chip.json"), 1) is None
    assert "no hetu_gdn_" in gdn.render(None)


def test_gdn_readers_on_a_traced_run_of_the_fixture(monkeypatch):
    """The five trace readers and the older ones the cell joins, through
    `for_run`, as the harness calls them: each returns a value."""
    cell = manifest.resolve(ROOT, CELL)
    fx = _fixture()
    tables = {gdn: gdn.reduce_scopes(fx, 1), moe: moe.reduce_moe(fx, 1),
              swa: swa.reduce_swa(fx, 1)}
    for mod, table in tables.items():
        assert table is not None, mod.__name__
        monkeypatch.setattr(mod, "for_run", lambda run, table=table: table)
    step = [[320] * 32 + [0] * 480] * 4
    run = {"cell": cell, "trace": {"steps": 1},
           "device": {"kind": "TPU v5 lite"},
           "counters": {"traced_picks": [step],
                        "gdn": {"chunk_log_decay_min": -1680.0}}}
    read = lambda name: manifest.reader(cell, name).read(run)
    by = tables[gdn]["scope_ms_per_step"]
    total = lambda *scopes: sum(sum(by[s].values()) for s in scopes)
    assert read("gdn_proj_ms_per_step.tokens") == pytest.approx(
        total(gdn.PROJ))
    assert read("gdn_conv_gate_ms_per_step.tokens") == pytest.approx(
        total(gdn.CONV, gdn.GATE))
    assert read("gdn_scan_ms_per_step.tokens") == pytest.approx(
        total(gdn.SCAN))
    assert read("gdn_time_pct.tokens") == pytest.approx(
        100 * total(*gdn.SCOPES) / tables[gdn]["device_self_ms_per_step"])
    assert 0 < read("gdn_scan_roofline_pct.tokens") < 100
    assert read("gdn_scan_roofline_pct.tokens") == pytest.approx(
        gdn.scan_roofline_pct(total(gdn.SCAN), cell.config,
                              cell.traffic, "TPU v5 lite"))
    assert read("gdn_chunk_log_decay_min") == -1680.0
    for name in ("moe_time_pct.tokens", "moe_experts_ms_per_step.tokens",
                 "moe_route_dispatch_combine_ms_per_step.tokens",
                 "moe_held_experts_roofline_pct.tokens",
                 "attn_gate_ms_per_step.tokens"):
        value = read(name)
        assert value is not None and value > 0, name
    # without the scopes (the parent of PR 68, any other model): nothing
    monkeypatch.setattr(gdn, "for_run", lambda run: None)
    for name in sorted(NEW_METRICS - {"gdn_chunk_log_decay_min"}):
        assert read(name) is None, name
    monkeypatch.undo()
    assert gdn.for_run({"cell": cell, "trace": None}) is None
