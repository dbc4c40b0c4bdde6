"""keye-vl-2.0-30b-a3b on the flagship trunk: learned sparse attention (a
lightning indexer picks top_k keys a query, trained by its own KL loss) over
softmax-routed experts, at a head width that is not d_model // n_heads.

The system against the benchmark's float32 reference on seeded weights at a
tiny size with top_k < T, so that the selection bites: hidden states, loss,
L_I, the kept sets, every gradient leaf; the indexer's leaves get nothing
from the cross-entropy and the trunk's nothing from L_I; with top_k >= T the
mixer is the dense `attention` kind bit for bit; the eight shares of an
expert layer add up to the uncut layer; `d_head` round-trips through
`hf_keye`; the kept-pair counter against its closed form; the scopes;
refusals by name (the other cells' lowered steps: test_cell_digests.py)."""
import dataclasses
import functools
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.kernels import dsa, flash_attention as fa
from hetu_tpu.models import generate, hf_keye, transformer as tfm
from hetu_tpu.parallel import pipeline
from hetu_tpu.telemetry import tracing
from model_harness import (ROOT, grads_of_loss, jitted, load_reference,
                           refuses, rel, round_trip, seeded_params,
                           seeded_tokens, sub_jaxprs)

reference = load_reference("keye-vl-2.0-30b-a3b")

ASSUMED = {"router_aux_loss_coef": 0.01, "router_z_loss_coef": 0.001,
           "indexer_loss_coef": 1.0}
# the published keys at a small size, every expert held: 4 query heads of 16
# columns on a 32-wide stream (q is 64 wide), top_k 8 of 32 or 64 keys
HF = dict(
    attention_bias=False, decoder_sparse_step=1, head_dim=16,
    hidden_act="silu", hidden_size=32, intermediate_size=64,
    max_position_embeddings=256, max_window_layers=2, mlp_only_layers=[],
    model_type="KeyeVL2", moe_intermediate_size=24, norm_topk_prob=True,
    num_attention_heads=4, num_experts=8, num_experts_per_tok=2,
    num_hidden_layers=2, num_key_value_heads=2, num_local_experts=8,
    rms_norm_eps=1e-6,
    rope_scaling={"mrope_section": [2, 3, 3], "rope_type": "default",
                  "type": "default"},
    rope_theta=10000000,
    sa_config={"indexer_head_dim": 8, "indexer_num_heads": 4,
               "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
               "q_chunk_size": 512, "topk": 8},
    sliding_window=None, tie_word_embeddings=False, use_sliding_window=False,
    vocab_size=96, assumed=ASSUMED)
# one chip's share: experts 2 and 3 of the 8
SHARE = {**HF, "num_experts": 2, "num_local_experts": 2,
         "num_routed_experts": 8, "first_expert_held": 2}
CONFIGS = {"whole": HF, "share": SHARE}
INDEXER = tuple(hf_keye.hf_name(0, hf_keye.VECTORS[n]) for n in (
    "k_idx_norm_scale", "k_idx_norm_bias")) + tuple(
    hf_keye.hf_name(0, hf_keye.LINEARS[n]) for n in (
        "wq_idx", "wk_idx", "ww_idx"))


# seeded weights, every vector off its initial 1 or 0 so that it matters, the
# indexer's Linears larger so that its scores spread
_params = functools.partial(
    seeded_params, bias=None, tenfold=("wq_idx", "wk_idx", "ww_idx"),
    noisy=lambda name: name.endswith(("_scale", "_norm"))
    or name == "k_idx_norm_bias")


def _kept_sets(params, tokens, cfg):
    """The system's kept set of every layer, [L x (B, T, T) bool] by query
    and the same by key, from the packed masks its own forward pass makes."""
    sets = []
    h = tfm.embed_tokens(params, tokens, cfg)
    for i in range(cfg.n_layers):
        p = jax.tree.map(lambda x: x[i], params["blocks"])
        x = tfm._norm(h, p["ln1_scale"], p["ln1_bias"], cfg)
        (by_query, by_key), _ = dsa.select(*tfm._dsa_index(x, p, cfg),
                                           cfg.dsa.top_k)
        sets.append((fa.unpack_row_mask(by_query),
                     fa.unpack_row_mask(by_key)))
        h, _ = tfm._block(h, p, cfg, None, kind="dsa")
    return sets


def _routing_terms(params, tokens, cfg):
    return tfm.moe_routing_stats(params, tokens, cfg, terms=True)


# -- the loader ------------------------------------------------------------------

def test_config_from_hf_reads_the_cell_and_the_small_size():
    cfg = hf_keye.config_from_hf(HF)
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim,
            cfg.d_head) == (32, 4, 2, 16, 16)
    assert cfg.dsa == tfm.DSAConfig(n_heads=4, head_dim=8, top_k=8)
    assert tfm.layer_runs(cfg) == (("dsa", 2),)
    assert cfg.router == tfm.Router(normalize=True, normalize_eps=0.0)
    assert cfg.qk_norm == "head" and cfg.rope and cfg.rope_theta == 1e7
    with open(os.path.join(
            ROOT, "benchmark/configs/keye-vl-2.0-30b-a3b/config.json")) as f:
        cell = hf_keye.config_from_hf(json.load(f), dtype=jnp.bfloat16)
    assert (cell.d_model, cell.n_heads, cell.kv_heads, cell.head_dim,
            cell.n_layers, cell.vocab_size) == (2048, 32, 4, 128, 4, 19072)
    assert cell.dsa == tfm.DSAConfig(n_heads=16, head_dim=64, top_k=2048)
    assert (cell.n_experts, cell.router.width, cell.router.first_held,
            cell.n_experts_per_tok, cell.d_ff_expert) == (16, 128, 0, 8, 768)
    shapes = jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cell))
    b = shapes["blocks"]
    assert b["wqkv"].shape == (4, 2048, 4096 + 2 * 512)
    assert b["wo"].shape == (4, 4096, 2048)
    assert (b["wq_idx"].shape, b["wk_idx"].shape, b["ww_idx"].shape) == (
        (4, 2048, 1024), (4, 2048, 64), (4, 2048, 16))
    assert b["router"].shape == (4, 2048, 128)
    assert b["w1"].shape == (4, 16, 2048, 768)
    # 465.7M parameters = 7.45 GB of state at 16 bytes each
    n = tfm.count_params(shapes) - sum(
        b[k].size for k in ("b1", "b2", "ln1_bias", "ln2_bias")) - 2048
    assert round(n / 1e6, 1) == 465.7
    # a width that IS d_model // n_heads stays derived
    assert hf_keye.config_from_hf({
        **HF, "head_dim": 8, "rope_scaling": {
            "mrope_section": [1, 1, 2], "rope_type": "default"}}).d_head == 0


@pytest.mark.parametrize("key,value,named", [
    ("attention_bias", True, "attention_bias"),
    ("use_sliding_window", True, "use_sliding_window"),
    ("mlp_only_layers", [0], "mlp_only_layers"),
    ("decoder_sparse_step", 2, "decoder_sparse_step"),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}, "rope_scaling"),
    ("rope_scaling", {"mrope_section": [2, 2, 2], "rope_type": "default"},
     "mrope_section"),
    ("sa_config", {**HF["sa_config"], "indexer_num_kv_heads": 2},
     "indexer_num_kv_heads"),
])
def test_loader_refuses_by_name(key, value, named):
    refuses(lambda: hf_keye.config_from_hf({**HF, key: value}), named,
            NotImplementedError)


def test_head_dim_of_its_own_round_trips_through_the_loader():
    """(iv): q is n_heads * d_head = 64 wide on a 32-wide stream; the
    weights copied out under their HF names and back are the same tree, and
    `wo` reads 64 columns."""
    cfg = hf_keye.config_from_hf(SHARE)
    assert cfg.n_heads * cfg.head_dim == 2 * cfg.d_model
    params = _params(cfg)
    sd = round_trip(hf_keye, params, cfg)
    assert sd["model.layers.1.self_attn.q_proj.weight"].shape == (64, 32)
    assert sd["model.layers.1.self_attn.k_proj.weight"].shape == (32, 32)
    assert sd["model.layers.1.self_attn.o_proj.weight"].shape == (32, 64)
    assert sd["model.layers.0.self_attn.indexer.wq.weight"].shape == (32, 32)
    assert "model.layers.0.mlp.experts.2.up_proj.weight" in sd
    assert "model.layers.0.mlp.experts.0.up_proj.weight" not in sd
    back = hf_keye.params_from_state_dict(sd, cfg, xp=jnp)
    tokens, targets = seeded_tokens(SHARE, 4)
    assert float(tfm.loss_fn(back, tokens, targets, cfg)) == float(
        tfm.loss_fn(params, tokens, targets, cfg))


# -- the system against the reference ---------------------------------------------

@pytest.mark.parametrize("which,T", [("whole", 32), ("share", 64)])
def test_system_matches_reference_hidden_loss_kept_sets_and_gradients(
        which, T):
    hf = CONFIGS[which]
    cfg = hf_keye.config_from_hf(hf)
    params = _params(cfg)
    tokens, targets = seeded_tokens(hf, 1, T=T)
    sd = hf_keye.state_dict_from_params(params, cfg)
    want_loss, want = reference.loss_terms(sd, tokens, targets, hf)
    # the kept sets: equal, float32 on both sides; and they bite
    kept = []
    for by_query, by_key in jitted(_kept_sets, cfg)(params, tokens):
        np.testing.assert_array_equal(np.asarray(by_key),
                                      np.asarray(by_query).swapaxes(1, 2))
        kept.append(by_query)
    for ours, theirs in zip(kept, want["kept"]):
        np.testing.assert_array_equal(np.asarray(ours), np.asarray(theirs))
        assert int(ours[0].sum()) == sum(min(t + 1, 8) for t in range(T))
    stats = jitted(tfm.dsa_stats, cfg)(params, tokens)
    np.testing.assert_allclose(np.asarray(stats["loss"]),
                               np.asarray(want["index_loss"]), rtol=2e-5)
    assert float(stats["loss"].min()) > 1e-3
    loss = jitted(tfm.loss_fn, cfg)(params, tokens, targets)
    assert abs(float(loss) - float(want_loss)) < 3e-6
    hidden, aux = jitted(tfm.forward_hidden, cfg)(params, tokens)
    assert rel(hidden, want["hidden"][-1]) < 2e-6
    np.testing.assert_allclose(
        np.asarray(aux), [float(want[k].sum()) for k in (
            "balance", "z", "index_loss")], rtol=2e-5)
    # the reference handed the system's sets and picks says the same
    routed = jitted(_routing_terms, cfg)(params, tokens)
    picks = routed["experts"]
    given_loss, given = reference.loss_terms(sd, tokens, targets, hf,
                                             kept=kept, picks=list(picks))
    assert abs(float(given_loss) - float(want_loss)) < 1e-6
    # `terms`: the rows each router read; its picks are their k largest logits
    logits = np.einsum("lsd,lde->lse", np.asarray(routed["router_in"],
                                                  np.float64),
                       np.asarray(params["blocks"]["router"], np.float64))
    np.testing.assert_array_equal(
        np.sort(np.asarray(picks), -1),
        np.sort(np.argsort(-logits, -1)[..., :picks.shape[-1]], -1))
    # every gradient leaf
    grads = hf_keye.state_dict_from_params(
        jitted(grads_of_loss, cfg)(params, tokens, targets), cfg)
    _, want_grads = reference.grads_of(list(sd))(sd, tokens, targets, hf)
    for n in sd:
        assert rel(grads[n], want_grads[n]) < 3e-5, n
    # the lean gradient is jax.grad of the plain forward
    few = [INDEXER[2], INDEXER[0], "model.layers.1.mlp.gate.weight",
           "model.layers.1.self_attn.k_proj.weight"]
    plain = jax.grad(lambda part: reference.loss_terms(
        {**sd, **part}, tokens, targets, hf)[0])({n: sd[n] for n in few})
    for n in few:
        assert rel(want_grads[n], plain[n]) < 1e-5, n


def test_indexer_learns_from_its_loss_alone_and_the_trunk_not_from_it():
    """(i): the gradient of the loss WITHOUT L_I is zero on the indexer's
    leaves, and the gradient of L_I alone is zero on every other leaf."""
    cfg = hf_keye.config_from_hf(HF)
    params = _params(cfg)
    tokens, targets = seeded_tokens(HF, 2)

    def parts(params):
        hidden, aux = tfm.forward_hidden(params, tokens, cfg)
        return tfm.loss_fn(params, tokens, targets, cfg) - aux[2], aux[2]

    rest = jax.jit(jax.grad(lambda p: parts(p)[0]))(params)
    index = jax.jit(jax.grad(lambda p: parts(p)[1]))(params)
    for path, g in jax.tree_util.tree_leaves_with_path(index):
        name = path[-1].key
        if name in tfm.DSA_LEAVES:
            assert float(jnp.abs(g).max()) > 1e-7, name
            assert float(jnp.abs(rest["blocks"][name]).max()) == 0.0, name
        else:
            assert float(jnp.abs(g).max()) == 0.0, name
    # ... which the trunk's leaves do get from the rest
    for name in ("wqkv", "wo", "router", "w1"):
        assert float(jnp.abs(rest["blocks"][name]).max()) > 1e-6
    # the whole gradient is the sum of the two
    whole = jitted(grads_of_loss, cfg)(params, tokens, targets)
    for w, r, i in zip(*(jax.tree.leaves(t) for t in (whole, rest, index))):
        np.testing.assert_allclose(np.asarray(w), np.asarray(r + i),
                                   atol=1e-7)


def _rematted_ops(jaxpr, scope):
    """Equations under ``scope`` inside a checkpoint's rematted computation,
    nested jaxprs included."""
    n = 0
    for e in jaxpr.eqns:
        stack = str(e.source_info.name_stack).split("/")
        n += "rematted_computation" in stack and scope in stack
        n += sum(_rematted_ops(sub, scope) for sub in sub_jaxprs(e))
    return n


@pytest.mark.parametrize("remat,limit,names", [
    pytest.param(False, None, (), id="no-remat"),
    # the CPU reports no limit: the bare checkpoint runs the chain again
    pytest.param(True, None, (), id="bare-checkpoint"),
    # room for the leaves' gradient (any limit has) and, over state and the
    # layer inputs, for the selection's packed bits (2 layers x 2 x 32 x 32
    # words = 16 KiB): the chain and the selection run once
    pytest.param(True, 5 << 18, (tracing.REMAT_DSA_GRADS,
                                 tracing.REMAT_DSA_MASK),
                 id="checkpoint-keeps-the-gradient"),
    # 16.3 KiB over state and inputs: the gradient's 11 KiB, not the bits'
    # 16 more. The selection IS run again
    pytest.param(True, 1000 << 10, (tracing.REMAT_DSA_GRADS,),
                 id="checkpoint-has-no-room-for-the-bits"),
])
def test_indexer_loss_and_every_gradient_under_remat(monkeypatch, remat,
                                                     limit, names):
    """L_I and the gradient of every leaf, the indexer's five and the
    trunk's, against AUTODIFF of the loss's own expressions (the rule taken
    off: ``indexer_loss``'s primal), with and without the trunk's checkpoint;
    and what the checkpoint runs again: the loss's whole chain when bare,
    nothing under ``hetu_dsa_loss`` once the gradient's name is kept, and
    nothing of the indexer at all (its projections, the index scores, the
    selection) once the packed kept set is kept too (the ``dot`` path reads
    it by query alone; the flash path's ``by_key_of``: the next test): where
    the limit has no room for the bits, all three still run for the mask."""
    cfg = hf_keye.config_from_hf(HF)
    params = _params(cfg)
    tokens, targets = seeded_tokens(HF, 5)

    def value(params, cfg):
        _, aux = tfm.forward_hidden(params, tokens, cfg)
        return tfm.loss_fn(params, tokens, targets, cfg), aux[2]

    with monkeypatch.context() as m:
        m.setattr(dsa, "indexer_loss", dsa.indexer_loss.fun)
        # a jit of its own: traced here, under the patch
        (_, want_index), want = jax.jit(jax.value_and_grad(
            value, has_aux=True), static_argnums=1)(params, cfg)
    monkeypatch.setattr(tfm, "_device_bytes_limit", lambda: limit)
    run = dataclasses.replace(cfg, remat=remat)
    got_names, held, budget = tfm._remat_names(
        run, params, tfm.embed_tokens(params, tokens, cfg), None)
    assert got_names == names
    # no ordered candidate rides along: the budget they are held to is spent
    assert not limit or budget < 0 < held
    (_, index), got = jax.jit(jax.value_and_grad(
        value, has_aux=True), static_argnums=1)(params, run)
    np.testing.assert_allclose(float(index), float(want_index), rtol=1e-6)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        name = path[-1].key
        assert g.dtype == w.dtype and g.shape == w.shape, name
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=1e-4,
            atol=2e-6 * float(jnp.abs(w).max()), err_msg=name)
        if name in tfm.DSA_LEAVES:
            assert float(jnp.abs(w).max()) > 1e-3, name
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: value(p, run)[0]))(params)
    again = {scope: _rematted_ops(jaxpr.jaxpr, scope) for scope in (
        tracing.SCOPE_DSA_LOSS, tracing.SCOPE_DSA_SELECT,
        tracing.SCOPE_DSA_PROJ, tracing.SCOPE_DSA_SCORES,
        tracing.SCOPE_BLK_ATTN)}
    assert (again[tracing.SCOPE_DSA_LOSS] > 0) == (remat and not names), again
    for scope in (tracing.SCOPE_DSA_SELECT, tracing.SCOPE_DSA_PROJ,
                  tracing.SCOPE_DSA_SCORES):
        assert (again[scope] > 0) == (
            remat and tracing.REMAT_DSA_MASK not in names), again
    # the attention itself the backward pass always runs again
    assert (again[tracing.SCOPE_BLK_ATTN] > 0) == remat, again


def test_the_backward_kernels_mask_is_made_from_the_kept_bits(monkeypatch):
    """On the flash path the backward kernel reads the kept set BY KEY, which
    nothing in the forward pass does: with the bits by query kept by name,
    the recomputation makes it from THEM (``by_key_of`` under
    ``hetu_dsa_select``) and evaluates no index score and no projection. Made
    from the unnamed array instead, it would take the whole selection again
    (my chip run, PR 64: 0.25 GiB kept and nothing saved)."""
    monkeypatch.setattr(dsa, "ROWS", 128)
    cfg = dataclasses.replace(
        hf_keye.config_from_hf(SHARE), attn_impl="flash", remat=True,
        dsa=tfm.DSAConfig(n_heads=4, head_dim=8, top_k=32))
    params = _params(cfg)
    tokens, targets = seeded_tokens(SHARE, 2, T=256)
    monkeypatch.setattr(tfm, "_device_bytes_limit", lambda: 1 << 30)
    names, _, _ = tfm._remat_names(
        cfg, params, tfm.embed_tokens(params, tokens, cfg), None)
    assert names[:2] == (tracing.REMAT_DSA_GRADS, tracing.REMAT_DSA_MASK)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: tfm.loss_fn(p, tokens, targets, cfg)))(params)
    again = {scope: _rematted_ops(jaxpr.jaxpr, scope) for scope in (
        tracing.SCOPE_DSA_SELECT, tracing.SCOPE_DSA_PROJ,
        tracing.SCOPE_DSA_SCORES, tracing.SCOPE_DSA_LOSS)}
    assert again[tracing.SCOPE_DSA_SELECT] > 0, again
    for scope in (tracing.SCOPE_DSA_PROJ, tracing.SCOPE_DSA_SCORES,
                  tracing.SCOPE_DSA_LOSS):
        assert again[scope] == 0, again


def test_top_k_of_all_keys_is_the_dense_attention_kind_bit_for_bit():
    """(ii): with top_k >= T the kept set is the causal triangle, and the
    mixer's output on the same weights is `_attention`'s, to the bit; the
    counter reads 100 %."""
    cfg = dataclasses.replace(
        hf_keye.config_from_hf(HF),
        dsa=tfm.DSAConfig(n_heads=4, head_dim=8, top_k=32))
    dense = dataclasses.replace(cfg, layer_types=(), dsa=None)
    params = _params(cfg)
    tokens, _ = seeded_tokens(HF, 3)
    p = jax.tree.map(lambda x: x[0], params["blocks"])
    h = tfm._norm(tfm.embed_tokens(params, tokens, cfg), p["ln1_scale"],
                  p["ln1_bias"], cfg)
    out, loss, kept = tfm._dsa_parts(h, p, cfg, None)
    want = tfm._attention(h, {k: v for k, v in p.items()
                              if k not in tfm.DSA_LEAVES}, dense, None)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    assert kept.tolist() == [32 * 33 // 2] * 2 and float(loss) > 0
    stats = tfm.dsa_stats(params, tokens, cfg)
    assert (stats["kept"].sum(-1) == 2 * stats["causal"]).all()
    # the whole stack too (two compiled scans: to rounding), whose aux
    # carries L_I and nothing else moves
    a, _ = tfm.forward_hidden(params, tokens, cfg)
    b, _ = tfm.forward_hidden(
        {**params, "blocks": {k: v for k, v in params["blocks"].items()
                              if k not in tfm.DSA_LEAVES}}, tokens, dense)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-7)


def test_flash_path_is_the_dot_path(monkeypatch):
    """The trunk with the kernels forced on (interpreted here), the kept
    sets reaching them as packed row masks, two blocks of rows a sequence:
    the same loss and gradients as the dot path under the boolean mask."""
    monkeypatch.setattr(dsa, "ROWS", 128)
    cfg = dataclasses.replace(
        hf_keye.config_from_hf(SHARE),
        dsa=tfm.DSAConfig(n_heads=4, head_dim=8, top_k=32))
    params = _params(cfg)
    tokens, targets = seeded_tokens(SHARE, 2, T=256)
    assert dsa.row_block(256) == 128
    flash = dataclasses.replace(cfg, attn_impl="flash")
    a, ga = jax.jit(jax.value_and_grad(
        lambda p: tfm.loss_fn(p, tokens, targets, cfg)))(params)
    b, gb = jax.jit(jax.value_and_grad(
        lambda p: tfm.loss_fn(p, tokens, targets, flash)))(params)
    assert abs(float(a) - float(b)) < 2e-6
    for x, y in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=2e-6)


# -- the selection ---------------------------------------------------------------

def test_select_rows_is_the_exact_top_k_with_ties_to_the_lower_key():
    rng = np.random.RandomState(0)
    R, T, K, first = 16, 48, 8, 20
    scores = rng.randn(R, T).astype(np.float32)
    scores[3, :9] = 0.5                 # ties above the threshold's rank
    scores[5, :] = 0.0                  # every key ties
    scores[7, 2:12] = -0.0              # -0.0 ties with 0.0
    scores[7, 12:30] = 0.0
    scores[9] = np.abs(scores[9]) * -1e-30      # tiny, all negative
    scores[11, 4] = np.inf
    keep = np.asarray(dsa.select_rows(jnp.asarray(scores), first, K))
    for r in range(R):
        t = first + r
        order = sorted(range(min(t, T - 1) + 1),
                       key=lambda s: (-scores[r, s], s))[:K]
        want = np.zeros(T, bool)
        want[order] = True
        np.testing.assert_array_equal(keep[r], want, err_msg=str(r))
    # every key while t < top_k
    early = np.asarray(dsa.select_rows(jnp.asarray(scores), 0, K))
    for r in range(K):
        assert early[r].tolist() == [s <= r for s in range(T)]


@pytest.mark.parametrize("T,top_k", [(64, 8), (256, 32), (1024, 64)])
def test_kept_pair_counter_against_its_closed_form(T, top_k):
    """sum_t min(t + 1, top_k) pairs a sequence, whatever the scores; the
    packed pair is `pack_row_mask`'s of the same set (1,024: two planes)."""
    key = jax.random.PRNGKey(T)
    qI = jax.random.normal(key, (2, T, 4 * 8))
    kI = jax.random.normal(jax.random.fold_in(key, 1), (2, T, 8))
    w = jax.random.normal(jax.random.fold_in(key, 2), (2, T, 4))
    (by_query, by_key), kept = jax.jit(
        lambda *a: dsa.select(*a, top_k))(qI, kI, w)
    assert kept.tolist() == [sum(min(t + 1, top_k) for t in range(T))] * 2
    assert by_query.shape == (2, T, T // fa.mask_planes(T))
    keep = fa.unpack_row_mask(by_query)
    want = fa.pack_row_mask(keep)
    np.testing.assert_array_equal(np.asarray(by_key), np.asarray(want[1]))
    # the cell's: 23.4 % of the causal pairs at 16,384 and 2,048
    cell = sum(min(t + 1, 2048) for t in range(16384))
    assert cell == 31_458_304 and round(
        100 * cell / (16384 * 16385 // 2), 1) == 23.4


# -- the shares add up -------------------------------------------------------------

def test_the_eight_shares_of_an_expert_layer_add_up_to_the_whole():
    """(iii): one expert layer of 16 experts cut in EIGHT shares of 2: the
    parts of the eight (the system's `_moe_mlp` told its share) sum to the
    UNCUT reference's layer, and the reference given the same share says the
    same as each."""
    hf = {**HF, "num_experts": 16, "num_local_experts": 16}
    whole_cfg = hf_keye.config_from_hf(hf)
    params = _params(whole_cfg)
    p = jax.tree.map(lambda x: x[1], params["blocks"])
    sd = hf_keye.state_dict_from_params(params, whole_cfg)
    w = {n[len("model.layers.1."):]: v for n, v in sd.items()
         if n.startswith("model.layers.1.")}
    m = jax.random.normal(jax.random.PRNGKey(5), (2, 32, 32))
    rows = m.reshape(-1, 32)
    want, _, want_aux = reference._moe_math(rows, w, hf, 0, None)
    whole, aux = tfm._moe_mlp(m, p, whole_cfg, None)
    np.testing.assert_allclose(np.asarray(whole.reshape(-1, 32)),
                               np.asarray(want), atol=2e-6)
    np.testing.assert_allclose(np.asarray(aux), np.asarray(want_aux),
                               rtol=1e-5)
    parts = []
    for first in range(0, 16, 2):
        share = {**hf, "num_experts": 2, "num_routed_experts": 16,
                 "first_expert_held": first}
        cfg = hf_keye.config_from_hf(share)
        held = {**p, **{k: p[k][first:first + 2] for k in
                        ("w1", "w3", "w2", "b1", "b2")}}
        out, _ = tfm._moe_mlp(m, held, cfg, None)
        same, _, _ = reference._moe_math(rows, w, share, first, None)
        np.testing.assert_allclose(np.asarray(out.reshape(-1, 32)),
                                   np.asarray(same), atol=2e-6)
        parts.append(out.reshape(-1, 32))
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(want),
                               atol=4e-6)
    assert float(jnp.max(jnp.abs(parts[0] - want))) > 1e-3


# -- the defaults stay what they were (the other cells' lowered steps:
# test_cell_digests.py) -------------------------------------------------------

def test_default_config_has_no_indexer_and_a_derived_head_width():
    cfg = tfm.TransformerConfig(n_layers=2)
    assert cfg.d_head == 0 and cfg.head_dim == 64 and cfg.dsa is None
    assert tfm._aux_size(cfg) == 2
    blocks = jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))["blocks"]
    assert not set(tfm.DSA_LEAVES) & set(blocks)
    assert blocks["wo"].shape == (2, 512, 512)
    with pytest.raises(ValueError, match="a dsa layer takes `dsa` sizes"):
        tfm.TransformerConfig(n_layers=1, layer_types=("dsa",))


# -- scopes ----------------------------------------------------------------------

def test_scopes_of_the_indexer_the_selection_and_its_loss_in_the_step():
    """Every part carries its scope in the COMPILED step: the projections
    forward and transposed, the selection forward (it has no gradient), the
    loss with its own pass over the index scores as the INNER scope; no two
    scope names contain one another (a reader takes the innermost segment),
    and docs/OBSERVABILITY.md names all four."""
    cfg = hf_keye.config_from_hf(SHARE)
    params = _params(cfg)
    tokens, targets = seeded_tokens(SHARE, 8)
    text = tfm.make_train_step(cfg).lower(
        params, tfm.init_opt_state(params), tokens,
        targets).compile().as_text()
    names = set(re.findall(r'op_name="([^"]+)"', text))
    for scope in tracing.DSA_SCOPES:
        under = [n for n in names if f"/{scope}/" in n]
        assert any(f"/jvp({tracing.SCOPE_FWD})/" in n for n in under), scope
    proj = [n for n in names if f"/{tracing.SCOPE_DSA_PROJ}/" in n]
    assert any(f"/transpose(jvp({tracing.SCOPE_FWD}))/" in n for n in proj)
    scores = [n for n in names if f"/{tracing.SCOPE_DSA_SCORES}/" in n]
    assert any(f"/{tracing.SCOPE_DSA_SELECT}/" in n for n in scores)
    assert any(f"/{tracing.SCOPE_DSA_LOSS}/" in n for n in scores)
    assert tracing.DSA_SCOPES == (
        "hetu_dsa_index_proj", "hetu_dsa_index_scores", "hetu_dsa_select",
        "hetu_dsa_loss")
    doc = open(os.path.join(ROOT, "docs", "OBSERVABILITY.md")).read()
    every = (tracing.DSA_SCOPES + tracing.MLA_SCOPES + tracing.BLOCK_SCOPES
             + tracing.MOE_SCOPES + tracing.SSM_SCOPES
             + tracing.SCONV_SCOPES + tracing.SSD_SCOPES
             + (tracing.STEP, tracing.SCOPE_FWD, tracing.SCOPE_OPT,
                tracing.SCOPE_EXIT, tracing.SCOPE_EMBED, tracing.SCOPE_HEAD,
                tracing.SCOPE_MOE_SHARED))
    for name in tracing.DSA_SCOPES:
        assert f"`{name}`" in doc, name
        for other in every:
            assert other == name or (name not in other
                                     and other not in name), (name, other)


# -- refusals by name -------------------------------------------------------------

def test_decode_pipeline_mesh_and_a_share_on_an_ep_mesh_refuse_by_name():
    cfg = hf_keye.config_from_hf(HF)
    refuses(lambda: generate._check_decode_args(cfg, 16, 0),
            "dsa=DSAConfig(")
    wide = dataclasses.replace(cfg, layer_types=(), dsa=None)
    refuses(lambda: generate._check_decode_args(wide, 16, 0), "d_head=16")
    with pytest.raises(NotImplementedError,
                       match=r"learned sparse attention \(dsa"):
        pipeline._make_stage_fn(cfg, 1)
    p = jax.tree.map(lambda x: x[0], _params(cfg)["blocks"])
    with pytest.raises(NotImplementedError, match="on a mesh or under a "
                                                  "padding mask"):
        tfm._dsa(jnp.zeros((1, 8, 32)), p, cfg, None,
                 attn_bias=jnp.zeros((1, 1, 1, 8)))
    devices = np.array(jax.devices()[:1]).reshape(1, 1, 1, 1)
    mesh = jax.sharding.Mesh(devices, ("dp", "tp", "sp", "ep"))
    share = hf_keye.config_from_hf(SHARE)
    held = {**p, **{k: p[k][2:4] for k in ("w1", "w3", "w2", "b1", "b2")}}

    class TwoWide:
        """A mesh whose `ep` axis says 2: the refusal reads the shape."""
        shape = {"dp": 1, "tp": 1, "sp": 1, "ep": 2}
        size, axis_names = 2, mesh.axis_names

    with pytest.raises(tfm.MoEConfigError, match="a share of an expert "
                                                 "layer"):
        tfm._routed_experts(jnp.zeros((1, 8, 32)), held, share, TwoWide())
