"""CPU tests of what ISSUE 63 added to the benchmark: the
smallthinker-21b-a3b adapter at a toy size against its reference (the four
parts of its check, on the timed step's own call), programs wrong on purpose
each read as not correct, the cell and its files, the step's FLOPs by hand,
the parent-style failure, and reduce/smallthinker.py with its two readers
(and the older readers the cell joins) on a timeline built by hand and on a
fixture cut from a TPU v5e trace of the cell. No number here is a device
number."""
import dataclasses
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import main, manifest            # noqa: E402
from benchmark.reduce import smallthinker                # noqa: E402
from benchmark.tests.test_benchmark import (             # noqa: E402,F401
    _last_line, _shrink, on_cpu, root)

CONFIG = "smallthinker-21b-a3b"
TRAFFIC = "pretrain-seq16384-b1-ep4share"
CELL = f"{CONFIG}.{TRAFFIC}"
NEW_METRICS = {"moe_route_early_ms_per_step.tokens",
               "moe_route_early_ahead_pct"}
JOINED = {
    "compiles_in_window.tokens", "device_idle_pct.tokens",
    "peak_hbm_gib.tokens", "mfu_pct", "fwd_ms_per_step.tokens",
    "recompute_ms_per_step.tokens", "bwd_ms_per_step.tokens",
    "opt_ms_per_step.tokens", "flash_attn_time_pct.tokens",
    "mosaic_time_pct.tokens", "moe_time_pct.tokens",
    "moe_experts_ms_per_step.tokens", "moe_load_max_over_mean",
    "moe_route_dispatch_combine_ms_per_step.tokens", "moe_held_pick_pct",
    "moe_held_experts_roofline_pct.tokens", "swa_time_pct.tokens",
    "swa_attn_roofline_pct.tokens", "full_attn_roofline_pct.tokens",
    "swa_computed_pair_pct", "attn_rope_ms_per_step.tokens",
    "setup_import_s", "setup_trace_lower_s", "setup_compile_s",
    "setup_cache_read_s", "setup_cache_miss_programs", "setup_programs",
    "setup_warmup_steps_s"}
# the real structure [global NoPE, window, window, window] at a width a CPU
# test can take: 4 heads on 2 k/v heads of 32, a window of 16 in 64 tokens, 2
# of 8 experts held from expert 2 on, top 3
TOY = {"hidden_size": 64, "moe_ffn_hidden_size": 48,
       "moe_intermediate_size": 48, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 32, "sliding_window_size": 16,
       "sliding_window": 16, "moe_num_primary_experts": 2, "num_experts": 2,
       "num_routed_experts": 8, "first_expert_held": 2,
       "moe_num_active_primary_experts": 3, "num_experts_per_tok": 3,
       "num_attention_heads_per_layer": [4, 4, 4, 4], "vocab_size": 512,
       "max_position_embeddings": 256}


def _fixture(name="smallthinker_one_chip.json"):
    with open(os.path.join(HERE, "fixtures", name)) as f:
        return json.load(f)


def _run_toy(root, capsys, seed=2 ** 31 + 63, trace=0):
    # lr 3e-4: at a toy width the gradients are under AdamW's eps scale at
    # the cell's 3e-6 and "the weights it left" would read float32's rounding
    assumed = manifest.resolve(ROOT, CELL).config["assumed"]
    _shrink(root, CONFIG, TRAFFIC,
            {**TOY, "assumed": {**assumed, "learning_rate": 3e-4}},
            {"sequences": 1, "seq_len": 64, "sync_every": 2,
             "warmup_steps": 3, "trace_steps": 2})
    rc = main.main(["--workload", CELL, "--seed", str(seed),
                    "--seconds", "0.5", "--trace", str(trace)],
                   root=str(root), t0=0.0)
    assert rc == 0
    return _last_line(capsys)


def test_smallthinker_adapter_runs_and_agrees_with_reference(root, on_cpu,
                                                             capsys):
    adapter = manifest.adapter(manifest.resolve(str(root), CELL))
    line = _run_toy(root, capsys)
    check = line["check"]
    # (A) each run of layers held to its own number
    assert list(check["hidden_rel_rms_err"]) == [
        "after_layer_0_attention", "after_layer_3_window"]
    assert max(check["hidden_rel_rms_err"].values()) < 2e-2
    assert check["loss_abs_err"] < adapter.LOSS_ABS_TOL
    assert set(check["grad_rel_rms_err"]) == set(adapter.GRAD_TOLS) == {
        "lnf_scale", "router", "wq", "wk", "wv", "wo", "embed", "head",
        "expert_gate", "expert_up", "expert_down", "norm"}
    assert all(err <= adapter.GRAD_TOLS[n]
               for n, err in check["grad_rel_rms_err"].items()), check
    assert set(check["update_rel_err"]) == set(adapter.GRAD_TOLS)
    assert check["dropped_picks"] == 0
    assert 0 < sum(check["held_picks"]) < 4 * 64 * 3
    # (B) every token's picks of four layers against float64 logits, and
    # their weights against float64 softmax over the picks' logits
    assert check["picks_checked"] == 4 * 64 * 3
    assert check["picks_differ_share"] <= adapter.PICKS_DIFFER_MAX_SHARE
    assert check["pick_weight_abs_err"] < adapter.PICK_WEIGHT_ABS_TOL
    # (C) the rotation, the absence of one, the kept keys, the window's edge
    assert check["own_rope_rel_rms_err_window"] < adapter.OWN_ROPE_REL_RMS_TOL
    assert check["own_nope_max_abs_diff"] == 0.0
    assert check["own_out_rel_rms_err_window"] < adapter.OWN_OUT_REL_RMS_TOL
    assert check["own_out_rel_rms_err_full"] < adapter.OWN_OUT_REL_RMS_TOL
    assert check["own_window_edge_share"] < adapter.OWN_WINDOW_EDGE_TOL
    # (D) the closed forms: a window of 16 in 64 tokens
    assert check["kept_pair_pct"]["window"] == pytest.approx(
        100 * (16 * 17 / 2 + 48 * 16) / (64 * 65 / 2))
    # which adjacent experts of the ring the chip holds is taken from the
    # picks, by rows alone (config.json `assumed.held_experts`): 2 of 8 here
    assert len(set(check["held_experts"])) == 2
    assert all(0 <= e < 8 for e in check["held_experts"])
    assert 0 < check["held_pick_pct_at_start"] < 100
    assert 0 < check["held_pick_pct_rehearsed"] < 100
    # the timed step's own call: the step after the window's
    assert check["step"] == line["window"]["steps"] + 3 + 1
    assert line["correct"], check
    assert line["window"]["compiles"] == 0
    assert line["failed"] == 0
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}


def test_the_held_experts_by_rows_alone_by_hand():
    """`_block_nearest_even` and `_one_exchanged`: 2 of 8 held, so the even
    share is a quarter; a block may wrap around the ring; one expert of it
    is exchanged only where that brings the share nearer."""
    import types
    import numpy as np
    adapter = manifest.adapter(manifest.resolve(ROOT, CELL))
    job = types.SimpleNamespace(cfg=types.SimpleNamespace(n_experts=2))
    block = lambda picks: adapter.SmallThinkerJob._block_nearest_even(
        job, np.asarray(picks, float)).tolist()
    exchanged = lambda picks, held: adapter.SmallThinkerJob._one_exchanged(
        job, np.asarray(picks, float), np.asarray(held)).tolist()
    #            0   1   2   3   4   5   6   7
    picks = [10, 30, 5, 5, 12, 13, 22, 5]          # 102 picks
    # picks on [o, o + 2): 40 35 10 17 25 35 27 15; a quarter is 25.5
    assert block(picks) == [4, 5]
    assert block(np.roll(picks, 3)) == [7, 0]
    assert block([20, 40, 5, 5, 5, 5, 15, 5]) == [7, 0]     # 25 of 100
    # [4, 5] holds 25: no exchange is nearer 25.5 (expert 4 for 0 holds 23)
    assert exchanged(picks, [4, 5]) == [4, 5]
    # [0, 1] holds 40: expert 1 (30) for expert 5 (13) holds 23
    assert exchanged(picks, [0, 1]) == [0, 5]
    # [2, 3] holds 10: expert 2 (5) for expert 6 (22) holds 27 (3 for 6 too:
    # the first found)
    assert exchanged(picks, [2, 3]) == [6, 3]


@pytest.mark.parametrize("seed", [2 ** 31 + 63, 7])
def test_the_job_starts_from_the_seeded_draw_with_its_experts_held(
        root, seed, monkeypatch):
    """Both looks of `assumed.held_experts` leave the seeded draw: the
    embedding at `assumed.embedding_std`, the routers' columns in an order
    in which the experts taken are the ones held, every other leaf and
    AdamW's state as drawn (the rehearsal's steps are thrown away), and the
    experts the initial picks' block with at most one exchanged."""
    import jax
    import numpy as np
    from benchmark.harness.spans import Spans
    from hetu_tpu.models import transformer as tfm
    assumed = manifest.resolve(ROOT, CELL).config["assumed"]
    _shrink(root, CONFIG, TRAFFIC,
            {**TOY, "assumed": {**assumed, "learning_rate": 3e-4}},
            {"sequences": 1, "seq_len": 64, "batches": 2})
    cell = manifest.resolve(str(root), CELL)
    adapter = manifest.adapter(cell)
    monkeypatch.setattr(adapter, "REHEARSAL_STEPS", 3)
    batches = manifest.generator(cell).generate(cell.traffic, cell.config,
                                                seed)
    job = adapter.build(cell.config, cell.traffic, seed, jax.devices()[:1],
                        batches, Spans(enabled=False))
    cfg = job.cfg
    first, held, width = cfg.router.first_held, cfg.n_experts, 8
    assert float(job.opt["t"]) == 0.0 and job._i == 0
    assert len(set(job.held.tolist())) == held
    drawn = jax.jit(lambda k: tfm.init_params(k, cfg))(
        jax.random.PRNGKey(seed))
    for got, want in zip(tfm.run_blocks(cfg, job.params["blocks"]),
                         tfm.run_blocks(cfg, drawn["blocks"])):
        got_r, want_r = np.asarray(got["router"]), np.asarray(want["router"])
        # the held columns are the taken experts' draws, the others the rest
        np.testing.assert_array_equal(got_r[..., first:first + held],
                                      want_r[..., job.held])
        assert sorted(map(tuple, got_r.reshape(-1, width).T)) == sorted(
            map(tuple, want_r.reshape(-1, width).T))
        np.testing.assert_array_equal(got["wqkv"], want["wqkv"])
        np.testing.assert_array_equal(got["w2"], want["w2"])
    np.testing.assert_allclose(
        job.params["embed"],
        np.asarray(drawn["embed"]) * (assumed["embedding_std"] / 0.02),
        rtol=1e-6)
    assert float(np.std(job.params["embed"])) == pytest.approx(
        assumed["embedding_std"], rel=0.05)
    # the share reported is the held columns' share of every batch's picks
    picks = sum(np.asarray(tfm.moe_routing_stats(
        job.params, b["tokens"], cfg)["picks"]).sum(0) for b in batches)
    assert picks[first:first + held].sum() / picks.sum() == pytest.approx(
        job.held_at_start)
    # the first look, from the draw alone; the second exchanges one at most
    drawn["embed"] = job.params["embed"]
    at_start = sum(np.asarray(tfm.moe_routing_stats(
        drawn, b["tokens"], cfg)["picks"]).sum(0) for b in batches)
    look = job._block_nearest_even(at_start)
    assert len(set(look.tolist()) - set(job.held.tolist())) <= 1


def _of_cfg(name, **changes):
    """The job's config with fields replaced: a program another model's."""
    def wrong(monkeypatch):
        from hetu_tpu.models import hf_smallthinker as hs
        loader = hs.config_from_hf

        def other(c, **kw):
            cfg = loader(c, **kw)
            for name, change in changes.items():
                cfg = dataclasses.replace(cfg, **{name: change(cfg)})
            return cfg
        monkeypatch.setattr(hs, "config_from_hf", other)
    wrong.__name__ = name
    return wrong


_router_reads_the_mlp_halfs_input = _of_cfg(
    "_router_reads_the_mlp_halfs_input", router=lambda c: dataclasses.replace(c.router, input="mlp"))
_softmax_over_all_left_unnormalised = _of_cfg(
    "_softmax_over_all_left_unnormalised", router=lambda c: dataclasses.replace(c.router, normalize=False))
_rope_on_layer_0 = _of_cfg("_rope_on_layer_0", rope=lambda c: True)
_a_window_of_one_key_more = _of_cfg(
    "_a_window_of_one_key_more", window=lambda c: dataclasses.replace(
        c.window, window=c.window.window + 1))
_a_window_of_one_key_fewer = _of_cfg(
    "_a_window_of_one_key_fewer", window=lambda c: dataclasses.replace(
        c.window, window=c.window.window - 1))


def _router_reads_the_first_norms_output(monkeypatch):
    from hetu_tpu.models import transformer as tfm
    plan = tfm._plan_routing
    monkeypatch.setattr(tfm, "_plan_routing", lambda x, p, cfg: plan(
        tfm._rms_norm(x, p["ln1_scale"], cfg.ln_eps), p, cfg))


def _swiglu_for_reglu(monkeypatch):
    from hetu_tpu.models import transformer as tfm
    monkeypatch.setattr(tfm, "_reglu", tfm._swiglu)


def _no_rope_on_a_window_layer(monkeypatch):
    from hetu_tpu.models import transformer as tfm
    monkeypatch.setattr(tfm, "_window_view", lambda cfg: dataclasses.replace(
        cfg, n_heads=cfg.window.n_heads, rope=False))


def _head_h_on_kv_head_h_mod_g(monkeypatch):
    """Query head h reads k/v head h mod G where it should read h // (H /
    G): the repeat tiled."""
    from hetu_tpu.models import transformer as tfm
    split = tfm._split_heads

    def tiled(qkv, p, cfg, mesh, impl):
        q, k, v = split(qkv, p, cfg, mesh, impl)
        B, T, _ = k.shape
        G, hd = cfg.kv_heads, cfg.head_dim
        retile = lambda x: x.reshape(B, T, G, -1, hd).transpose(
            0, 1, 3, 2, 4).reshape(B, T, -1)
        return q, retile(k), retile(v)
    monkeypatch.setattr(tfm, "_split_heads", tiled)


def _the_absent_experts_part_added(monkeypatch):
    """Every pick is computed: an absent expert e by the held expert e mod
    the experts held (weights of the same distribution stand in)."""
    import jax.numpy as jnp
    from hetu_tpu.models import transformer as tfm
    route = tfm._route

    def all_held(x, p, cfg):
        top_p, top_e, counts, probs, aux = route(x, p, cfg)
        n, first = cfg.n_experts, cfg.router.first_held
        folded = jnp.sum(counts.reshape(-1, n), 0)
        counts = jnp.zeros_like(counts).at[first:first + n].set(folded)
        return top_p, top_e % n + first, counts, probs, aux
    monkeypatch.setattr(tfm, "_route", all_held)


def _the_steps_own_picks_wrong(monkeypatch):
    """ONLY the compiled step: every second token takes the logits ranked 2
    to k + 1; the routing pass the check's part (B) and the reference's picks
    come from (`moe_routing_stats`) stays sound. What of the check sees the
    STEP's own picks: the gradient it applied."""
    import jax
    import jax.numpy as jnp
    from hetu_tpu.models import transformer as tfm
    route, make, real = tfm._route, tfm.make_train_step, jax.lax.top_k
    in_step = []

    def top_k(a, k):
        v, i = real(a, k + 1)
        odd = (jnp.arange(a.shape[0]) % 2 == 1)[:, None]
        return (jnp.where(odd, v[:, 1:], v[:, :k]),
                jnp.where(odd, i[:, 1:], i[:, :k]))

    def wrong_route(x, p, cfg):
        if not in_step:
            return route(x, p, cfg)
        with monkeypatch.context() as m:
            m.setattr(jax.lax, "top_k", top_k)
            return route(x, p, cfg)

    def wrong_step(cfg, **kw):
        step = make(cfg, **kw)

        def call(*args):
            in_step.append(True)        # the step is traced inside its call
            try:
                return step(*args)
            finally:
                in_step.pop()
        return call
    monkeypatch.setattr(tfm, "_route", wrong_route)
    monkeypatch.setattr(tfm, "make_train_step", wrong_step)


def _state_left_unchanged(monkeypatch):
    from hetu_tpu.models import transformer as tfm
    monkeypatch.setattr(tfm, "adamw_update", lambda params, grads, opt, lr: (
        params, {**opt, "t": opt["t"] + 1.0}))


# the parts of the check that tell at a TOY width, where the experts add a
# hundredth of the stream (on the chip the stream and the loss tell too:
# adapter.py has those readings)
@pytest.mark.parametrize("wrong,names", [
    (_router_reads_the_mlp_halfs_input, ("grads",)),
    (_router_reads_the_first_norms_output, ("grads",)),
    (_swiglu_for_reglu, ("grads",)),
    (_rope_on_layer_0, ("own_nope", "grads")),
    (_no_rope_on_a_window_layer, ("own_rope", "grads")),
    (_a_window_of_one_key_more, ("own_window_edge",)),
    (_a_window_of_one_key_fewer, ("own_window_edge",)),
    (_softmax_over_all_left_unnormalised, ("pick_weights", "grads")),
    (_head_h_on_kv_head_h_mod_g, ("own_out", "grads")),
    (_the_absent_experts_part_added, ("grads",)),
    (_the_steps_own_picks_wrong, ("grads",)),
    (_state_left_unchanged, ("grads", "update"))],
    ids=lambda x: getattr(x, "__name__", None))
def test_the_smallthinker_check_holds_the_timed_step(root, on_cpu, capsys,
                                                     monkeypatch, wrong,
                                                     names):
    """A step wrong on purpose is seen by the check, which compares what the
    job's own compiled step returned: `correct` false, by the named parts."""
    wrong(monkeypatch)
    line = _run_toy(root, capsys)
    check = line["check"]
    assert not line["correct"]
    assert set(names) <= set(check["failed_parts"]), check["failed_parts"]


def test_smallthinker_cell_resolves_with_its_per_layer_metrics():
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
    # `moe_act_ms_per_step.tokens` and `moe_rows_per_held_expert` would read
    # this cell unedited, but `benchmark/tests/test_nemotron_h.py` pins their
    # lists with `==` (PERF.md section 7): the cell stays off them
    for absent in ("block_mlp_ms_per_step.tokens", "head_ms_per_step.tokens",
                   "step_named_pct.tokens", "attn_gate_ms_per_step.tokens",
                   "moe_act_ms_per_step.tokens", "moe_rows_per_held_expert",
                   "moe_shared_ms_per_step.tokens",
                   "moe_experts_roofline_pct.tokens",
                   "flash_attn_roofline_pct.tokens"):
        assert absent not in names, absent
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "setup_s"}
    for m in cell.per_layer:
        assert callable(manifest.reader(cell, m["name"]).read)
    m = manifest.load(ROOT)
    assert [p["name"] for p in m["per_layer"][-2:]] == [
        "moe_route_early_ms_per_step.tokens", "moe_route_early_ahead_pct"]
    for p in m["per_layer"]:
        if p["name"] in NEW_METRICS:
            assert p["workloads"] == [CELL] and p["moves"] == "tokens_per_s"
    # the catalog row's keys, the cut, and nothing else changed: the two
    # layouts stay WHOLE
    c = cell.config
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SmallThinker-21BA3B-Instruct")
    assert c["source"] == row["source_url"]
    published = row["config"]
    cut = {"num_hidden_layers": 4, "moe_num_primary_experts": 16,
           "vocab_size": 38016}
    assert {k: c[k] for k in published} == {**published, **cut}
    assert len(c["rope_layout"]) == len(c["sliding_window_layout"]) == 52
    assert c["rope_layout"][:4] == c["sliding_window_layout"][:4] == [
        0, 1, 1, 1]
    assert (c["num_routed_experts"], c["first_expert_held"],
            c["num_experts"]) == (64, 0, 16)
    # the copies an accepted reader takes a published key by
    assert (c["num_experts"], c["num_experts_per_tok"],
            c["moe_intermediate_size"], c["sliding_window"]) == (
        c["moe_num_primary_experts"], c["moe_num_active_primary_experts"],
        c["moe_ffn_hidden_size"], c["sliding_window_size"])
    assert c["layer_types"] == ["full_attention"] + ["sliding_attention"] * 3
    assert c["num_attention_heads_per_layer"] == [28] * 4
    assert list(c["reduced"]) == list(cut)
    for key, said in (("num_hidden_layers", "52"),
                      ("moe_num_primary_experts", "64"),
                      ("vocab_size", "151,936")):
        assert f"published {said}" in c["reduced"][key], key
    assert "4 CHIPS" in c["deployment"]
    for key in ("router_input", "secondary_experts", "router_losses",
                "embedding_std", "held_experts", "initial_routing",
                "learning_rate", "adamw", "vocabulary_padding", "tokens",
                "reader_keys", "weight_names"):
        assert key in c["assumed"], key
    assert "llm_build_smallthinker" in c["assumed"]["router_input"]
    assert c["assumed"]["learning_rate"] == 3e-06
    # the routers' start (PERF.md section 6): keye's accepted embedding
    # scale, the held block by rows alone, and what still moves the share
    assert c["assumed"]["embedding_std"] == 1.0
    assert "ROWS ALONE" in c["assumed"]["held_experts"]
    assert "drift" in c["assumed"]["initial_routing"]
    entry = next(e for e in m["configs"] if e["name"] == CONFIG)
    assert entry["reduced"] == list(cut) and entry["source"] == c["source"]
    # no other cell reports this configuration's metrics
    for other in ("olmoe-1b-7b.pretrain-seq4096",
                  "laguna-xs.2.pretrain-seq16384-b1-ep8share"):
        assert not NEW_METRICS & {
            p["name"] for p in manifest.resolve(ROOT, other).per_layer}


def test_smallthinker_step_flops_by_hand():
    c = manifest.resolve(ROOT, CELL).config
    T, D = 16384, 2560
    by = smallthinker.forward_flops(c, T)
    causal = T * (T + 1) // 2
    kept = 4096 * 4097 // 2 + (T - 4096) * 4096
    assert (causal, kept) == (134225920, 58722304)
    assert round(100 * kept / causal, 2) == 43.75
    assert by["global_core"] == 4 * 128 * 28 * causal
    assert by["window_core"] == 3 * 4 * 128 * 28 * kept
    assert by["attention_proj"] == T * 4 * (
        2 * D * (3584 + 2 * 512) + 2 * 3584 * D)
    assert by["router"] == T * 4 * 2 * D * 64
    # the held picks at the even share 6 * 16 / 64 a token, three matrices
    assert by["experts"] == T * 4 * 1.5 * 6 * D * 768
    assert by["head"] == T * 2 * D * 38016
    tera = {k: round(v / 1e12, 2) for k, v in by.items()}
    assert tera == {"global_core": 1.92, "window_core": 2.53,
                    "attention_proj": 2.75, "router": 0.02, "experts": 1.16,
                    "head": 3.19}
    forward = sum(by.values())
    assert round(forward / 1e12, 1) == 11.6
    cores = by["global_core"] + by["window_core"]
    assert round(100 * (cores + by["attention_proj"]) / forward) == 62
    assert smallthinker.train_flops_per_token(c, T) == pytest.approx(
        (3 * (forward - cores) + 3.5 * cores) / T)
    # the accepted readers at THIS shape through the alias keys
    from benchmark.reduce import lfm2, swa
    assert swa.layers_of(c) == {"full_attention": (1, 28),
                                "sliding_attention": (3, 28)}
    assert swa.pairs_of(c, T) == {"sliding_attention": kept,
                                  "full_attention": causal}
    assert lfm2.held_expert_matmul_flops(1536 * 16, D, 768) == (
        2 * 1536 * 16 * D * 768)


def test_smallthinker_pick_counter_readers():
    cell = manifest.resolve(ROOT, CELL)
    # two traced steps, four layers, 64 experts: the sixteen held take 1,536
    # rows each in the first step and 768 in the second
    step = lambda rows: [[rows] * 16 + [7] * 48] * 4
    run = {"cell": cell, "trace": None,
           "counters": {"traced_picks": [step(1536), step(768)]}}
    read = manifest.reader(cell, "moe_rows_per_held_expert").read
    assert read(run) == pytest.approx(1152.0)
    assert manifest.reader(cell, "moe_held_pick_pct").read(run) == (
        pytest.approx(100 * 1152 * 16 / (1152 * 16 + 7 * 48)))
    assert read({**run, "counters": {}}) is None
    pairs = {"window": {"computed": 3, "kept": 2}}
    assert manifest.reader(cell, "swa_computed_pair_pct").read(
        {**run, "counters": {"attn_pairs": pairs}}) == 150.0


def test_smallthinker_cell_on_a_program_without_the_loader_fails_cleanly(
        root, on_cpu, capsys, monkeypatch):
    """The parent of PR 63 under this PR's benchmark files: `build` raises
    a ManifestError (no loader), the harness exits non-zero in one line, and
    nothing hangs."""
    import hetu_tpu.models
    monkeypatch.setitem(sys.modules, "hetu_tpu.models.hf_smallthinker", None)
    monkeypatch.delattr(hetu_tpu.models, "hf_smallthinker", raising=False)
    adapter = manifest.adapter(manifest.resolve(str(root), CELL))
    with pytest.raises(manifest.ManifestError, match="no loader"):
        adapter.build({}, {}, 0, [None], [], None)
    rc = main.main(["--workload", CELL, "--seed", "1", "--seconds", "0.5",
                    "--trace", "0"], root=str(root), t0=0.0)
    assert rc != 0


# -- the routing's place in the timeline -----------------------------------------

FWD = "jit(<lambda>)/jvp(hetu_fwd)/while/body/closed_call/checkpoint/"
REMAT = ("jit(<lambda>)/transpose(jvp(hetu_fwd))/while/body/closed_call/"
         "checkpoint/rematted_computation/")
EARLY = "hetu_moe_route_early/hetu_moe_route/"
FLASH = ('%flash_fwd.{n} = bf16[1,64,128] custom-call(bf16[1,64,128] %a), '
         'custom_call_target="tpu_custom_call"')


def _layer(t, n, ahead, scope="hetu_swa_attn"):
    """One layer's forward ops from time `t` (ns): two routing ops of 10 and
    20, a flash call of 100, two expert ops of 50; the routing before the
    flash call, or its second op behind it."""
    route = [[f"%fusion.{n}0 = f32[64,8] fusion(%x)", 0, 10,
              FWD + EARLY + "dot_general"],
             [f"%sort.{n}1 = s32[192] sort(%y)", 0, 20, FWD + EARLY + "sort"]]
    flash = [FLASH.format(n=n), 0, 100,
             FWD + scope + "/flash_fwd/pallas_call:"]
    experts = [[f"%fusion.{n}2 = bf16[192,48] fusion(%z)", 0, 50,
                FWD + "hetu_moe_experts/dot_general"],
               [f"%fusion.{n}3 = bf16[192,64] fusion(%w)", 0, 50,
                FWD + "hetu_moe_experts/hetu_moe_act/mul"]]
    order = (route + [flash] if ahead else [route[0], flash, route[1]])
    ops = []
    for op in order + experts:
        ops.append([op[0], float(t), float(op[2]), op[3]])
        t += op[2] + 5
    return ops, t


def test_routing_ahead_of_attention_from_a_timeline_built_by_hand():
    ops, t = [], 1000
    for n, ahead in enumerate((True, True, False, True)):
        layer, t = _layer(t, n, ahead,
                          "hetu_blk_attn" if n == 0 else "hetu_swa_attn")
        ops += layer
    # the forward run again under remat, routing BEHIND its flash call: its
    # order is the backward pass's and is not counted
    ops += [[FLASH.format(n=9), float(t), 100.0,
             REMAT + "hetu_swa_attn/flash_fwd/pallas_call:"],
            ["%fusion.99 = f32[64,8] fusion(%x)", float(t + 105), 10.0,
             REMAT + EARLY + "dot_general"]]
    layers = smallthinker.ahead_of_attention(ops)
    assert [l["ahead"] for l in layers] == [True, True, False, True]
    assert [l["route_ops"] for l in layers] == [2, 2, 2, 2]
    assert layers[0]["flash_start"] - layers[0]["route_end"] == 5.0
    r = smallthinker.reduce_early(
        {"chips": [{"chip": 0, "modules": [], "ops": ops}], "host": []}, 1)
    assert (r["layers"], r["ahead"], r["ahead_pct"]) == (4, 3, 75.0)
    assert r["early_ms_per_step"] == {"fwd": pytest.approx(4 * 30e-6),
                                      "recompute": pytest.approx(10e-6),
                                      "bwd": 0.0}
    assert "3 of 4 expert layers" in smallthinker.render(r)
    # a program without the scope: nothing, and no raise
    plain = [[op[0], op[1], op[2], op[3].replace("hetu_moe_route_early/", "")]
             for op in ops]
    assert smallthinker.reduce_early(
        {"chips": [{"chip": 0, "modules": [], "ops": plain}], "host": []},
        1) is None
    assert "no hetu_moe_route_early" in smallthinker.render(None)
    cell = manifest.resolve(ROOT, CELL)
    for name in NEW_METRICS:
        assert manifest.reader(cell, name).read(
            {"cell": cell, "trace": None, "counters": {}}) is None


def _phase(op_name):
    if "transpose(" not in op_name:
        return "fwd"
    return "recompute" if "rematted_computation" in op_name else "bwd"


def test_smallthinker_table_from_the_fixture():
    """Every expected number is worked out here from the fixture's lines (a
    cut of the cell's own trace on the v5e): the scope is found in forward,
    recomputed and backward ops, AROUND `hetu_moe_route`, which the older
    readers still count; and in both layers the compiler ran the router's
    matmul and the top k AHEAD of the flash call and sank the sort and the
    share's plan BEHIND it, so neither layer's routing is ahead."""
    from benchmark.reduce import moe
    fx = _fixture()
    ops = fx["chips"][0]["ops"]
    table = smallthinker.reduce_early(fx, steps=1)
    under = [op for op in ops if "/hetu_moe_route_early/" in op[3]]
    assert under and len(under) < len(ops)
    assert table["device_self_ms_per_step"] == pytest.approx(
        sum(op[2] for op in ops) / 1e6)
    for p in smallthinker.PHASES:
        want = sum(op[2] for op in under if _phase(op[3]) == p) / 1e6
        assert want > 0, p
        assert table["early_ms_per_step"][p] == pytest.approx(want), p
    # the older reader of the four MoE scopes counts it as routing
    assert {moe.scope_of(op[3]) for op in under} == {"hetu_moe_route"}
    assert all("/hetu_moe_route_early/hetu_moe_route/" in op[3]
               for op in under)
    flash = [op for op in ops if "flash_fwd" in op[0]]
    assert ["hetu_blk_attn" in flash[0][3], "hetu_swa_attn" in flash[1][3]
            ] == [True, True]
    layers = smallthinker.ahead_of_attention(ops)
    assert len(layers) == 2 and not any(l["ahead"] for l in layers)
    for l, call in zip(layers, flash):
        assert l["flash_start"] == call[1]
        # it starts before the flash call and ends after it
        assert l["route_start"] < call[1] < call[1] + call[2] < l["route_end"]
        mine = [op for op in under if _phase(op[3]) == "fwd"
                and l["route_start"] <= op[1] < l["route_end"]]
        assert len(mine) == l["route_ops"] == 13
        before = [op for op in mine if op[1] + op[2] <= call[1]]
        after = [op for op in mine if op[1] >= call[1] + call[2]]
        assert len(before) + len(after) == 13
        assert any("dot_general" in op[3] for op in before)
        assert any("top_k" in op[3] for op in before)
        assert all("dot_general" not in op[3] and "top_k" not in op[3]
                   for op in after)
        assert any("argsort" in op[3] for op in after)
        assert l["ahead_ns"] == sum(op[2] for op in before)
    assert (table["layers"], table["ahead"], table["ahead_pct"]) == (2, 0, 0.0)
    assert 50 < table["ahead_time_pct"] < 75
    assert table["lead_us_p50"] < 0
    text = smallthinker.render(table)
    assert "hetu_moe_route_early" in text and "0 of 2 expert layers" in text
    # a trace without the scope (nemotron's, laguna's) reads as nothing
    for other in ("nemotron_h_one_chip.json", "swa_one_chip.json"):
        assert smallthinker.reduce_early(_fixture(other), 1) is None


def test_smallthinker_readers_on_a_traced_run_of_the_fixture(monkeypatch):
    """The two new readers and the older ones the cell joins that read these
    ops, through `for_run`, as the harness calls them."""
    from benchmark.reduce import moe
    cell = manifest.resolve(ROOT, CELL)
    fx = _fixture()
    tables = {smallthinker: smallthinker.reduce_early(fx, 1),
              moe: moe.reduce_moe(fx, 1)}
    for mod, table in tables.items():
        assert table is not None, mod.__name__
        monkeypatch.setattr(mod, "for_run", lambda run, table=table: table)
    run = {"cell": cell, "trace": {"steps": 1},
           "device": {"kind": "TPU v5 lite"}, "counters": {}}
    read = lambda name: manifest.reader(cell, name).read(run)
    by = tables[smallthinker]["early_ms_per_step"]
    assert read("moe_route_early_ms_per_step.tokens") == pytest.approx(
        sum(by.values()))
    assert read("moe_route_early_ahead_pct") == 0.0
    # the routing's time is in the older reader's sum too
    assert read("moe_route_dispatch_combine_ms_per_step.tokens") >= (
        read("moe_route_early_ms_per_step.tokens") - 1e-9)
    # without the scope (the parent of PR 63, any other model): nothing
    monkeypatch.setattr(smallthinker, "for_run", lambda run: None)
    for name in NEW_METRICS:
        assert read(name) is None, name
    # an end-to-end run has no trace
    monkeypatch.undo()
    assert smallthinker.for_run({"cell": cell, "trace": None}) is None
