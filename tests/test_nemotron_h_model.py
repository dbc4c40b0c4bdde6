"""The `nemotron_h` tower of Nemotron-Labs-TwoTower-30B-A3B on the flagship
trunk (ISSUE 55), at small sizes on the CPU with seeded weights: a stack of
SINGLE sublayers `MEMEM*EME` (Mamba-2 in 4 groups with the gated norm by
group, NoPE grouped-query attention 4 on 2 at a head width of its own, 8
ungated relu^2 experts, top 3, beside a shared one) against
`benchmark/configs/nemotron-twotower-30b-a3b/reference.py`: the loader, the
system against the reference (loss, hidden states after each sublayer, every
gradient), the sixteen shares of an expert layer, the grouped norm against
the whole-width one, relu^2 through a share's row loops, `_ssd` at the cell's
groups against the time recurrence, the scopes and the refusals. The
reference's mixer against `transformers`' `Mamba2Mixer` is in
test_references_against_transformers.py, every cell's lowered program in
test_cell_digests.py. No number here is a device number."""
import dataclasses
import functools
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.models import generate, hf_nemotron_h as hn, transformer as tfm
from hetu_tpu.parallel import pipeline
from hetu_tpu.telemetry import tracing
from model_harness import (ROOT, grads_of_loss, hidden_after_runs, jitted,
                           load_reference, refuses, rel, round_trip,
                           seeded_params, seeded_tokens)

CONFIG = "benchmark/configs/nemotron-twotower-30b-a3b/config.json"


reference = load_reference("nemotron-twotower-30b-a3b")

# the published keys at a small size, every expert held: T = 32 is four
# chunks of 8, two heads a group
HF = dict(
    model_type="nemotron_h", vocab_size=256, hidden_size=64,
    num_hidden_layers=9,
    hybrid_override_pattern="MEMEM*EMEMEM*EMEMEM*", num_attention_heads=4,
    num_key_value_heads=2, head_dim=32, mamba_num_heads=8, mamba_head_dim=16,
    ssm_state_size=16, n_groups=4, conv_kernel=4, chunk_size=8, expand=2,
    intermediate_size=48, moe_intermediate_size=48,
    moe_shared_expert_intermediate_size=96, n_routed_experts=8,
    n_shared_experts=1, num_experts_per_tok=3, routed_scaling_factor=2.5,
    norm_topk_prob=True, n_group=1, topk_group=1, layer_norm_epsilon=1e-5,
    max_position_embeddings=64, tie_word_embeddings=False,
    mlp_hidden_act="relu2", mamba_hidden_act="silu", use_conv_bias=True,
    use_bias=False, mlp_bias=False, attention_bias=False,
    mamba_proj_bias=False, time_step_min=0.001, time_step_max=0.1,
    time_step_floor=1e-4, time_step_limit=[0, None], rope_theta=10000,
    partial_rotary_factor=1)
# one chip's share: experts 2 and 3 of the 8
SHARE = {**HF, "n_routed_experts": 2, "num_routed_experts": 8,
         "first_expert_held": 2}
CONFIGS = {"whole": HF, "share": SHARE}
KINDS = ("mamba+alone", "mlp", "mamba+alone", "mlp", "mamba+alone",
         "attention+alone", "mlp", "mamba+alone", "mlp")


# seeded weights, the selection bias moved off zero so that it matters to the
# picks, and every scale (the layers' norms, the gated norm, D) off one so
# that a scale applied to the wrong channels shows
_params = functools.partial(
    seeded_params, noisy=("ln1_scale", "ln2_scale", "ssm_norm", "D"))


# -- the loader ------------------------------------------------------------------

def test_config_from_hf_reads_every_key_of_the_cell():
    with open(os.path.join(ROOT, CONFIG)) as f:
        c = json.load(f)
    cfg = hn.config_from_hf(c, dtype=jnp.bfloat16, router_bias_rate=0.03)
    assert tfm.layer_kinds(cfg) == KINDS and cfg.single_sublayer
    assert tfm.layer_runs(cfg) == tuple((k, 1) for k in KINDS)
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (
        2688, 32, 2, 128)
    assert not cfg.rope and not cfg.use_pos_emb and not cfg.tied_head
    assert (cfg.mlp, cfg.d_ff_expert, cfg.d_ff_shared) == ("relu2", 1856,
                                                           3712)
    assert cfg.ssm == tfm.SSMConfig(
        n_heads=64, head_dim=64, d_state=128, n_groups=8, d_conv=4,
        chunk=128, norm_groups=8, dt_init=(0.001, 0.1, 1e-4))
    assert cfg.router == tfm.Router(
        score="sigmoid", bias=True, normalize=True, normalize_eps=1e-20,
        scale=2.5, aux_losses=False, bias_rate=0.03, width=128, first_held=0)
    assert (cfg.n_experts, cfg.n_experts_per_tok, cfg.ln_eps) == (8, 6, 1e-5)
    shapes = jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    mamba, experts, attn = (shapes["blocks"][i] for i in (0, 1, 5))
    assert set(mamba) == {"ln1_scale", "ln1_bias", "w_in", "conv_w", "conv_b",
                          "dt_bias", "A_log", "D", "ssm_norm", "w_out"}
    assert mamba["w_in"].shape == (1, 2688, 4096 + 6144 + 64)
    assert set(attn) == {"ln1_scale", "ln1_bias", "wqkv", "wo"}
    assert attn["wqkv"].shape == (1, 2688, (32 + 2 * 2) * 128)
    assert set(experts) == {"ln2_scale", "ln2_bias", "router", "router_bias",
                            "w1", "w2", "ws1", "ws2"}
    assert experts["w1"].shape == (1, 8, 2688, 1856)
    assert experts["ws2"].shape == (1, 3712, 2688)
    assert experts["router"].shape == (1, 2688, 128)
    # the issue's count by hand, less the norms' unused bias leaves
    n = tfm.count_params(shapes)
    assert n == 666_990_336
    D = 2688
    m_layer = (D * 10304 + 5 * 6144 + 3 * 64 + 4096 + 4096 * D + D)
    a_layer = 2 * D * 4096 + 2 * D * 256 + D
    e_layer = 8 * 2 * D * 1856 + 2 * D * 3712 + D * 128 + 128 + D
    assert (m_layer, a_layer, e_layer) == (38_744_896, 23_399_040,
                                           100_125_440)
    by_hand = 4 * m_layer + a_layer + 4 * e_layer + 2 * 16384 * D + D
    assert n - by_hand == 10 * 2688          # ln*_bias a layer, lnf_bias
    assert jax.tree.structure(tfm.param_specs(cfg)) == jax.tree.structure(
        jax.tree.map(lambda x: 0, shapes))


@pytest.mark.parametrize("change,named", [
    ({"hybrid_override_pattern": "ME-EM*EME"}, "'-' layer"),
    ({"mlp_bias": True}, "mlp_bias=True"),
    ({"attention_bias": True}, "attention_bias=True"),
    ({"use_bias": True}, "use_bias=True"),
    ({"n_group": 2}, "n_group=2"),
    ({"use_conv_bias": False}, "use_conv_bias=False"),
    ({"mlp_hidden_act": "silu"}, "mlp_hidden_act='silu'"),
    ({"time_step_limit": [0.0, 0.5]}, "time_step_limit"),
    ({"n_shared_experts": 2}, "n_shared_experts=2"),
    ({"denoiser_config": {"adaln": True}}, "denoiser tower"),
    ({"block_length": 32}, "block-diffusion objective")])
def test_loader_refuses_by_name(change, named):
    with pytest.raises(NotImplementedError, match=re.escape(named)):
        hn.config_from_hf({**HF, **change})


@pytest.mark.parametrize("which", sorted(CONFIGS))
def test_state_dict_round_trip(which):
    cfg = hn.config_from_hf(CONFIGS[which])
    sd = round_trip(hn, _params(cfg), cfg,
                    back=lambda sd, cfg: hn.params_from_state_dict(
                        {k: np.asarray(v) for k, v in sd.items()}, cfg))
    first = cfg.router.first_held
    assert sd["backbone.layers.0.mixer.conv1d.weight"].shape == (
        128 + 2 * 4 * 16, 1, 4)
    assert sd["backbone.layers.5.mixer.k_proj.weight"].shape == (2 * 32, 64)
    assert sd[f"backbone.layers.1.mixer.experts.{first}.up_proj.weight"
              ].shape == (48, 64)
    assert sd["backbone.layers.1.mixer.gate.weight"].shape == (8, 64)
    assert "backbone.layers.1.mixer.experts.0.up_proj.weight" in sd or first


def test_dt_initialisation_is_mamba_ssms():
    cfg = hn.config_from_hf(HF)
    mamba = tfm.init_params(jax.random.PRNGKey(3), cfg)["blocks"][0]
    dt = np.asarray(jax.nn.softplus(mamba["dt_bias"]))
    assert dt.min() >= 1e-3 * (1 - 1e-5) and dt.max() <= 0.1 * (1 + 1e-5)
    assert np.std(np.log(dt)) > 0.3          # spread over the two decades
    np.testing.assert_allclose(np.asarray(mamba["A_log"][0]),
                               np.log(np.arange(1, 9)), rtol=1e-6)
    # the default leaves Granite's: dt_bias = 1
    plain = dataclasses.replace(cfg, ssm=dataclasses.replace(
        cfg.ssm, dt_init=None))
    assert np.all(np.asarray(tfm.init_params(
        jax.random.PRNGKey(3), plain)["blocks"][0]["dt_bias"]) == 1.0)


# -- the system against the reference --------------------------------------------

@functools.lru_cache(maxsize=None)
def _both_sides(which):
    """-> (params, tokens, targets, state dict, the reference's loss and
    terms) of CONFIGS[which] at seed 1: the reference side of the comparison,
    run once for the cases that share it."""
    hf = CONFIGS[which]
    cfg = hn.config_from_hf(hf)
    params = _params(cfg)
    tokens, targets = seeded_tokens(hf, 1)
    sd = hn.state_dict_from_params(params, cfg)
    return (params, tokens, targets, sd,
            *reference.loss_terms(sd, tokens, targets, hf))


@pytest.mark.parametrize("which", sorted(CONFIGS))
def test_system_matches_reference_hidden_loss_picks_and_gradients(which):
    hf = CONFIGS[which]
    cfg = hn.config_from_hf(hf, router_bias_rate=1e-3)
    params, tokens, targets, sd, want_loss, want = _both_sides(which)
    loss = jitted(tfm.loss_fn, cfg)(params, tokens, targets)
    assert abs(float(loss) - float(want_loss)) < 2e-6
    after = jitted(hidden_after_runs, cfg)(params, tokens)
    assert len(after) == len(want["hidden"]) == 9
    for i, got in enumerate(after):
        assert rel(got, want["hidden"][i]) < 2e-6, i
    stats = jitted(tfm.moe_routing_stats, cfg)(params, tokens)
    np.testing.assert_array_equal(
        np.sort(np.asarray(stats["experts"]), -1),
        np.sort(np.asarray(want["experts"]), -1))
    np.testing.assert_array_equal(np.asarray(stats["picks"]),
                                  np.asarray(want["counts"]))
    assert int(stats["dropped"].sum()) == 0
    # the picks handed in are taken as they are
    same_loss, same = reference.loss_terms(
        sd, tokens, targets, hf, picks=list(stats["experts"]))
    assert abs(float(same_loss) - float(want_loss)) < 1e-6
    np.testing.assert_array_equal(np.asarray(same["experts"]),
                                  np.asarray(stats["experts"]))
    grads = hn.state_dict_from_params(
        jitted(grads_of_loss, cfg)(params, tokens, targets), cfg)
    names = [n for n in sd if "e_score" not in n]
    lean_loss, lean_hidden, want_grads = reference.grads_of(names)(
        sd, tokens, targets, hf)
    assert float(lean_loss) == float(want_loss)
    for a, b in zip(lean_hidden, want["hidden"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for n in names:
        assert rel(grads[n], want_grads[n]) < 5e-5, n
    # the lean gradient (time segments under jax.checkpoint, a layer run
    # again) is jax.grad of the plain forward
    few = ["backbone.layers.0.mixer.A_log", "backbone.layers.0.mixer.dt_bias",
           "backbone.layers.1.mixer.gate.weight",
           "backbone.layers.5.mixer.k_proj.weight"]
    plain = jax.grad(lambda part: reference.loss_terms(
        {**sd, **part}, tokens, targets, hf)[0])({n: sd[n] for n in few})
    for n in few:
        assert rel(want_grads[n], plain[n]) < 1e-5, n
        assert float(jnp.max(jnp.abs(plain[n]))) > 1e-9, n


WRONG = {
    "the norm over all channels": (0, lambda c: dataclasses.replace(
        c, ssm=dataclasses.replace(c.ssm, norm_groups=1))),
    "the scale 2.5 left out": (1, lambda c: dataclasses.replace(
        c, router=dataclasses.replace(c.router, scale=1.0))),
    "SiLU for relu^2": (1, lambda c: c),
    "plain relu for relu^2": (1, lambda c: c),
    "a rotation applied to q and k": (5, lambda c: dataclasses.replace(
        c, rope=True)),
    # at the program's initial weights (taps of std 0.02) the recurrence is a
    # thousandth of D x, so the stream cannot tell: the gradient of A_log,
    # which reaches the loss through the recurrence alone, does
    "the state not carried across a chunk": (None, lambda c: c),
    "a head on the next group's B and C": (None, lambda c: c)}


@pytest.mark.parametrize("wrong", sorted(WRONG))
def test_the_comparison_sees_each_mechanism(wrong, monkeypatch):
    """Each mechanism wrong in the SYSTEM moves the residual stream off the
    reference's by far more than float32 allows, from the first layer that
    has it on (`first`); the two of the recurrence move layer 0's A_log
    gradient instead."""
    first, edit = WRONG[wrong]
    cfg = hn.config_from_hf(HF)
    params, tokens, targets, sd, _, terms = _both_sides("whole")
    want = terms["hidden"]
    if wrong == "SiLU for relu^2":
        monkeypatch.setattr(tfm, "_relu2", lambda u: jax.nn.silu(u))
    elif wrong == "plain relu for relu^2":
        monkeypatch.setattr(tfm, "_relu2", lambda u: jax.nn.relu(u))
    elif wrong == "the state not carried across a chunk":
        ssd = tfm._ssd
        monkeypatch.setattr(tfm, "_scan", lambda x, dt, A, Bm, Cm, chunk,
                            mesh=None: jnp.concatenate(
            [ssd(*(a[:, s:s + chunk] for a in (x, dt)), A,
                 *(a[:, s:s + chunk] for a in (Bm, Cm)), chunk)
             for s in range(0, x.shape[1], chunk)], 1))
    elif wrong == "a head on the next group's B and C":
        scan = tfm._scan
        monkeypatch.setattr(tfm, "_scan", lambda x, dt, A, Bm, Cm, chunk,
                            mesh=None: scan(x, dt, A, jnp.roll(Bm, 1, 2),
                                            jnp.roll(Cm, 1, 2), chunk, mesh))
    if first is None:
        name = "backbone.layers.0.mixer.A_log"
        got = hn.state_dict_from_params(jitted(grads_of_loss, cfg, wrong)(
            params, tokens, targets), cfg)[name]
        want = reference.grads_of([name])(sd, tokens, targets, HF)[2]
        assert rel(got, want[name]) > 0.05
        return
    got = jitted(hidden_after_runs, edit(cfg), wrong)(params, tokens)
    errs = [rel(g, w) for g, w in zip(got, want)]
    assert all(e < 2e-6 for e in errs[:first]) and errs[first] > 1e-4, errs


# -- the sixteen shares -------------------------------------------------------------

def test_the_sixteen_shares_of_an_expert_layer_add_up_to_the_whole():
    """One expert layer of 32 experts cut in SIXTEEN shares of 2: the routed
    parts of the sixteen and the shared expert ONCE sum to the UNCUT
    reference's layer; the reference given the same share says the same."""
    hf = {**HF, "n_routed_experts": 32}
    whole_cfg = hn.config_from_hf(hf)
    params = _params(whole_cfg)
    p = jax.tree.map(lambda x: x[0], params["blocks"][1])
    sd = hn.state_dict_from_params(params, whole_cfg)
    scope = "backbone.layers.1.mixer."
    w = {n[len(scope):]: v for n, v in sd.items() if n.startswith(scope)}
    m = jax.random.normal(jax.random.PRNGKey(5), (2, 32, 64))
    rows = m.reshape(-1, 64)
    want, _ = reference._experts_math(rows, w, hf, 0, None)
    whole, _ = tfm._moe_mlp(m, p, whole_cfg, None)
    np.testing.assert_allclose(np.asarray(whole.reshape(-1, 64)),
                               np.asarray(want), atol=2e-6)
    shared = reference._relu2(
        rows @ w["shared_experts.up_proj.weight"].T) @ w[
            "shared_experts.down_proj.weight"].T
    assert float(jnp.max(jnp.abs(shared))) > 1e-3
    routed = []
    for first in range(0, 32, 2):
        share = {**hf, "n_routed_experts": 2, "num_routed_experts": 32,
                 "first_expert_held": first}
        cfg = hn.config_from_hf(share)
        held = {**p, **{k: p[k][first:first + 2] for k in ("w1", "w2")}}
        out, _ = tfm._moe_mlp(m, held, cfg, None)
        same, _ = reference._experts_math(rows, w, share, first, None)
        np.testing.assert_allclose(np.asarray(out.reshape(-1, 64)),
                                   np.asarray(same), atol=2e-6)
        part, _ = tfm._moe_mlp(m, held, dataclasses.replace(
            cfg, d_ff_shared=0), None)
        routed.append(part.reshape(-1, 64))
    assert sum(float(jnp.max(jnp.abs(r))) > 1e-4 for r in routed) >= 12
    np.testing.assert_allclose(np.asarray(sum(routed) + shared),
                               np.asarray(want), atol=6e-6)


def test_relu2_experts_through_the_share_loops_are_the_every_row_form():
    """A share's row loops (dispatch, `_act_rows` of relu^2, combine) against
    the whole-array passes on the SAME weights: the uncut layer with the
    absent experts' output matrices zeroed. Values and every gradient."""
    whole_cfg = hn.config_from_hf({**HF, "moe_shared_expert_intermediate_size"
                                   : 0, "n_shared_experts": 0})
    cfg = hn.config_from_hf({**SHARE, "n_shared_experts": 0,
                             "moe_shared_expert_intermediate_size": 0})
    p = jax.tree.map(lambda x: x[0], _params(whole_cfg)["blocks"][1])
    m = jax.random.normal(jax.random.PRNGKey(7), (2, 32, 64))
    here = jnp.zeros((8, 1, 1)).at[2:4].set(1.0)

    def every_row(m, w1, w2):
        return jnp.sum(jnp.square(tfm._moe_mlp(
            m, {**p, "w1": w1, "w2": w2 * here}, whole_cfg, None)[0]))

    def loops(m, w1, w2):
        return jnp.sum(jnp.square(tfm._moe_mlp(
            m, {**p, "w1": w1, "w2": w2}, cfg, None)[0]))

    want, want_g = jax.value_and_grad(every_row, (0, 1, 2))(m, p["w1"],
                                                            p["w2"])
    got, got_g = jax.value_and_grad(loops, (0, 1, 2))(m, p["w1"][2:4],
                                                      p["w2"][2:4])
    assert float(want) > 1e-6 and abs(float(got) / float(want) - 1) < 1e-5
    assert rel(got_g[0], want_g[0]) < 1e-5
    assert rel(got_g[1], want_g[1][2:4]) < 1e-5
    assert rel(got_g[2], want_g[2][2:4]) < 1e-5


# -- the mixer ----------------------------------------------------------------------

@pytest.mark.parametrize("groups", [1, 2, 8])
def test_grouped_norm_is_granites_at_one_group_and_another_above(groups):
    y = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 128)) * jnp.linspace(
        0.1, 3.0, 128)
    scale = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(3), (128,))
    cfg = dataclasses.replace(
        hn.config_from_hf(HF), ssm=dataclasses.replace(
            hn.config_from_hf(HF).ssm, norm_groups=groups))
    got = tfm._mamba_gate_norm(y, {"ssm_norm": scale}, cfg)
    whole = tfm._rms_norm(y, scale, cfg.ln_eps)        # Granite's
    if groups == 1:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(whole))
    else:
        assert rel(got, whole) > 0.1
    want = reference._rms_by_group(y, scale, groups, cfg.ln_eps)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-6,
                               atol=1e-6)
    by_group = np.asarray(got / scale).reshape(2, 16, groups, -1)
    np.testing.assert_allclose(np.mean(by_group ** 2, -1), 1.0, rtol=1e-3)


def test_ssd_at_eight_groups_of_eight_heads_is_the_time_recurrence():
    """`_ssd` at the cell's own scan shape (64 heads of 64 in 8 groups, a
    state of 128, chunks of 128; three chunks) against the reference's
    recurrence position by position, with a decay slow enough that a chunk's
    state reaches the next."""
    B, T, H, P, G, N, Q = 1, 384, 64, 64, 8, 128, 128
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    x = jax.random.normal(ks[0], (B, T, H, P))
    Bm, Cm = (0.3 * jax.random.normal(k, (B, T, G, N)) for k in ks[1:3])
    dt = jnp.exp(jax.random.uniform(ks[3], (B, T, H), minval=np.log(1e-3),
                                    maxval=np.log(0.1)))
    A_log = jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32))
    with jax.default_matmul_precision("highest"):
        got = tfm._ssd(x, dt, A_log, Bm, Cm, Q)
        own = jnp.arange(H) // (H // G)
        want = reference._recurrence(x, Bm[:, :, own], Cm[:, :, own], dt,
                                     -jnp.exp(A_log))
        alone = tfm._ssd(x[:, Q:], dt[:, Q:], A_log, Bm[:, Q:], Cm[:, Q:], Q)
    assert rel(got, want) < 1e-5
    # the state carried in matters at these step sizes
    assert rel(alone[:, :Q], want[:, Q:2 * Q]) > 1e-2


# -- what the trunk counts and names ---------------------------------------------

def test_remat_counts_a_single_sublayers_one_sum_as_its_output():
    cfg = hn.config_from_hf(SHARE, dtype=jnp.bfloat16)
    params = jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    h = jax.ShapeDtypeStruct((2, 32, 64), jnp.bfloat16)
    names, held, budget = tfm._remat_names(cfg, params, h, None,
                                           bytes_limit=1 << 30)
    # no x1 / x2 to keep (a layer's one sum is what the scan keeps anyway);
    # the one attention layer's o and lse, q, k and v
    assert tracing.REMAT_X1 not in names and tracing.REMAT_X2 not in names
    assert names == (tracing.REMAT_ATTN_O, tracing.REMAT_ATTN_LSE,
                     tracing.REMAT_ATTN_Q, tracing.REMAT_ATTN_K,
                     tracing.REMAT_ATTN_V)
    act = 2 * 32 * 128 * 2              # (B, T, heads * head_dim) bfloat16
    assert held == act + 2 * 32 * 4 * 4 + act + 2 * act // 2
    assert tfm._aux_size(cfg) == 2


def test_scopes_of_the_activation_and_the_grouped_norm_in_the_step():
    cfg = hn.config_from_hf(SHARE, router_bias_rate=1e-3)
    params = _params(cfg)
    tokens, targets = seeded_tokens(SHARE, 8)
    text = tfm.make_train_step(cfg).lower(
        params, tfm.init_opt_state(params), tokens,
        targets).compile().as_text()
    names = set(re.findall(r'op_name="([^"]+)"', text))
    inside = {tracing.SCOPE_MOE_ACT: tracing.SCOPE_MOE_EXPERTS,
              tracing.SCOPE_SSM_GATE_NORM: tracing.SCOPE_SSM_GATE}
    for scope, outer in inside.items():
        under = [n for n in names if f"/{scope}/" in n]
        assert under and all(f"{outer}/{scope}/" in n for n in under), scope
        assert any(f"/jvp({tracing.SCOPE_FWD})/" in n for n in under), scope
        assert any(f"/transpose(jvp({tracing.SCOPE_FWD}))/" in n
                   for n in under), scope
    # the shared expert under its own scope, the block scopes inside it
    assert any(f"/{tracing.SCOPE_MOE_SHARED}/{tracing.SCOPE_BLK_MLP_UP}/" in n
               for n in names)
    doc = open(os.path.join(ROOT, "docs", "OBSERVABILITY.md")).read()
    for scope in inside:
        assert f"`{scope}`" in doc, scope
    # Granite's gate opens no scope of the norm's
    plain = dataclasses.replace(cfg, ssm=dataclasses.replace(
        cfg.ssm, norm_groups=1))
    text = jax.jit(lambda p, t: tfm.forward_hidden(p, t, plain)[0]).lower(
        params, tokens).compile().as_text()
    assert tracing.SCOPE_SSM_GATE_NORM not in text
    assert f"/{tracing.SCOPE_SSM_GATE}/" in text


def test_step_writes_the_picks_and_moves_the_bias_for_the_new_stack():
    cfg = hn.config_from_hf(SHARE, router_bias_rate=1e-2)
    params = _params(cfg, bias=0.0)
    tokens, targets = seeded_tokens(SHARE, 9)
    want = np.asarray(tfm.moe_routing_stats(params, tokens, cfg)["picks"])
    _, new, opt = tfm.make_train_step(cfg, lr=1e-4)(
        params, tfm.init_opt_state(params), tokens, targets)
    for j, i in enumerate((1, 3, 6, 8)):
        counted = np.asarray(opt["m"]["blocks"][i][tfm.ROUTER_BIAS][0])
        np.testing.assert_array_equal(counted, want[j])
        np.testing.assert_allclose(
            np.asarray(new["blocks"][i][tfm.ROUTER_BIAS][0]),
            1e-2 * np.sign(want[j].mean() - want[j]), atol=1e-7)
    assert want.sum() == 4 * 2 * 32 * 3


# -- the refusals ---------------------------------------------------------------------

def test_decode_and_pipeline_refuse_by_name():
    cfg = hn.config_from_hf(HF)
    refuses(lambda: generate._check_decode_args(
        dataclasses.replace(cfg, layer_types=("attention",) * 9,
                            n_experts=0, d_ff_shared=0, d_head=0,
                            router=tfm.Router()), 16, 0),
        "single_sublayer=True")
    refuses(lambda: generate._check_decode_args(
        tfm.TransformerConfig(mlp="relu2"), 16, 0), "mlp='relu2'")
    with pytest.raises(NotImplementedError, match="unequal kinds"):
        pipeline._make_stage_fn(cfg, 1)
    one_kind = dataclasses.replace(cfg, layer_types=("attention",) * 9)
    assert tfm.layer_runs(one_kind) == (("attention+alone", 9),)
    with pytest.raises(NotImplementedError, match="single_sublayer=True"):
        pipeline._make_stage_fn(one_kind, 1)


@pytest.mark.parametrize("kw,named", [
    (dict(layer_types=("attention", "mlp"), n_layers=2), "single_sublayer"),
    (dict(single_sublayer=True, post_ln=True), "pre-LN"),
    (dict(single_sublayer=True, n_experts=2, n_dense_layers=1),
     "leading dense layers"),
    (dict(n_experts=2, d_ff_shared=8, mlp="gelu"), "SwiGLU or relu2")])
def test_config_refuses_by_name(kw, named):
    with pytest.raises(ValueError, match=named):
        tfm.TransformerConfig(**kw)


# -- the other configurations ---------------------------------------------------------

def test_defaults_leave_configs_what_they_were():
    cfg = tfm.TransformerConfig(n_layers=3)
    assert not cfg.single_sublayer and cfg.mlp == "gelu"
    assert tfm.layer_kinds(cfg) == ("attention",) * 3
    assert all(tfm.has_mlp(k) for k in tfm.layer_kinds(cfg))
    assert tfm.SSMConfig().norm_groups == 1 and tfm.SSMConfig().dt_init is None
    moe = tfm.TransformerConfig(n_experts=4, n_experts_per_tok=2, n_layers=2,
                                n_dense_layers=1)
    assert tfm.layer_kinds(moe) == ("attention+dense", "attention")
    assert [tfm.experts_of(moe, k) for k in tfm.layer_kinds(moe)] == [0, 4]
    assert tfm.mixer_of("mamba+alone") == "mamba" and tfm.mixer_of(
        "mlp") == "mlp"
