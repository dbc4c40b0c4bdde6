"""Granite 4.0-H (a hybrid stack: Mamba-2 layers beside grouped-query
attention without positions) on the flagship trunk, at toy sizes on the CPU:
the system against the float32 reference (hidden states, loss, every
gradient leaf by kind), the chunked SSD form against the recurrence over
time, and the refactor's contract that a homogeneous stack is the pytree and
the bits it was. The reference against ``transformers``' own forward is in
test_references_against_transformers.py.
"""
import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.kernels import ssd as ssd_kernel
from hetu_tpu.models import bert, hf_granite, hf_olmoe, hf_ouro
from hetu_tpu.models import transformer as tfm
from hetu_tpu.telemetry import tracing
from model_harness import ROOT, load_reference, refuses, rel

CONFIG_DIR = os.path.join(ROOT, "benchmark", "configs", "granite-4.0-h-micro")
reference = load_reference("granite-4.0-h-micro")

T = 32
# the published config's keys at toy widths; every kind of layer, 2 kv heads
# serving 4 query heads, 4 chunks of 8 positions
HF = dict(
    attention_bias=False, attention_multiplier=0.0625,
    embedding_multiplier=12, hidden_act="silu", hidden_size=64,
    layer_types=["mamba", "mamba", "attention", "mamba"], logits_scaling=8,
    mamba_chunk_size=8, mamba_conv_bias=True, mamba_d_conv=4,
    mamba_d_head=16, mamba_d_state=16, mamba_expand=2, mamba_n_groups=1,
    mamba_n_heads=8, mamba_proj_bias=False, max_position_embeddings=64,
    normalization_function="rmsnorm", num_attention_heads=4,
    num_hidden_layers=4, num_key_value_heads=2, num_local_experts=0,
    position_embedding_type="nope", residual_multiplier=0.22,
    rms_norm_eps=1e-5, shared_intermediate_size=128,
    tie_word_embeddings=True, vocab_size=256)


def _data(vocab, seed, batch=2, seq=T):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, vocab, (batch, seq + 1))
    return jnp.asarray(ids[:, :-1], jnp.int32), jnp.asarray(ids[:, 1:],
                                                            jnp.int32)


def _seeded(hf, seed):
    """(cfg, params): the program's initialiser, with the leaves it makes
    constant (A_log, dt_bias, D, the norms' scales, the convolution's bias)
    moved off their constants so that a wrong use of any of them shows."""
    cfg = hf_granite.config_from_hf(hf)
    params = tfm.init_params(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 1000))
    noise = lambda x, s: x + s * jax.random.normal(next(keys), x.shape)
    blocks = []
    for b in tfm.run_blocks(cfg, params["blocks"]):
        b = {k: (noise(v, 0.1) if k in ("A_log", "dt_bias", "D", "conv_b",
                                        "ssm_norm", "ln1_scale", "ln2_scale")
                 else v) for k, v in b.items()}
        blocks.append(b)
    params["blocks"] = tfm.blocks_of_runs(blocks)
    params["lnf_scale"] = noise(params["lnf_scale"], 0.1)
    return cfg, params


# -- the reference's own gradient ---------------------------------------------

def test_reference_grads_of_is_jax_grad_of_its_loss():
    """`grads_of` (each layer under `jax.checkpoint`) against `jax.grad` of
    the plain forward: the same float32 program run twice, so 1e-6 of each
    gradient's RMS."""
    cfg, params = _seeded(HF, 2)
    sd = hf_granite.state_dict_from_params(params, cfg)
    tokens, targets = _data(HF["vocab_size"], 3)
    names = ["model.layers.0.mamba.A_log", "model.embed_tokens.weight",
             "model.layers.2.self_attn.k_proj.weight"]
    got_loss, got = reference.grads_of(names)(sd, tokens, targets, HF)
    assert float(got_loss) == float(reference.loss_terms(
        sd, tokens, targets, HF)[0])
    want = jax.grad(lambda part: reference.loss_terms(
        {**sd, **part}, tokens, targets, HF)[0])({n: sd[n] for n in names})
    for n in names:
        assert rel(got[n], want[n]) < 1e-6, n


# -- the system against the reference -------------------------------------------

def _hf_grads_in_trunk_layout(cfg, grads_sd):
    """HF-named gradients -> the trunk's pytree (a pure relayout, linear)."""
    return hf_granite.params_from_state_dict(
        {k: np.asarray(v) for k, v in grads_sd.items()}, cfg)


def test_system_matches_reference_hidden_loss_and_every_gradient():
    """float32 compute on both sides at "highest" precision: what is left
    is summation order (the chunked form against the time scan, fused q|k|v
    against three matmuls). Hidden states after every layer within 1e-5 of
    their RMS, the loss within 1e-6, every gradient leaf within 1e-4 of its
    RMS (measured: 3e-7, 0, <= 2e-6). The tied embedding's gradient holds
    the lookup's and the head's parts."""
    cfg, params = _seeded(HF, 4)
    sd = hf_granite.state_dict_from_params(params, cfg)
    tokens, targets = _data(HF["vocab_size"], 5)
    want_loss, want = reference.loss_terms(sd, tokens, targets, HF)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(tfm.loss_fn)(params, tokens,
                                                      targets, cfg)
        # the residual stream after each run of layers, by running the
        # stack's prefixes
        runs = tfm.run_blocks(cfg, params["blocks"])
        for r, n in ((1, 2), (2, 3), (3, 4)):
            sub = dataclasses.replace(
                cfg, n_layers=n, layer_types=cfg.layer_types[:n])
            h, _ = tfm.forward_hidden(
                {**params, "blocks": tfm.blocks_of_runs(runs[:r])},
                tokens, sub)
            assert rel(h, want["hidden"][n - 1]) < 1e-5, n
    assert abs(float(loss) - float(want_loss)) < 1e-6
    assert np.isfinite(float(loss))
    names = sorted(n for n in sd if n != "lm_head.weight")
    want_grads = _hf_grads_in_trunk_layout(
        cfg, {**reference.grads_of(names)(sd, tokens, targets, HF)[1],
              "lm_head.weight": np.zeros_like(sd["lm_head.weight"])})
    unused = {"ln1_bias", "ln2_bias", "b1", "b2", "lnf_bias"}
    seen = set()
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        leaf = path[-1].key
        w = want_grads
        for k in path:
            w = w[k.key if hasattr(k, "key") else k.idx]
        if leaf in unused:
            assert not np.any(np.asarray(g)), leaf
            continue
        seen.add(leaf)
        assert np.sqrt(np.mean(np.asarray(w) ** 2)) > 0, leaf
        assert rel(g, w) < 1e-4, (jax.tree_util.keystr(path), rel(g, w))
    assert seen == {"A_log", "dt_bias", "D", "conv_w", "conv_b", "w_in",
                    "w_out", "ssm_norm", "wqkv", "wo", "w1", "w2", "w3",
                    "ln1_scale", "ln2_scale", "lnf_scale", "embed"}


ORDERS = {"mamba_first": ["mamba", "mamba", "attention", "attention"],
          "attention_first": ["attention", "mamba", "mamba", "mamba"],
          "alternating": ["mamba", "attention", "mamba", "attention"]}


# the widths the Mosaic kernels of `kernels/ssd.py` slice: heads of 64
# columns, a state of 128, eight heads a grid step, two chunks of 128
KERNEL_WIDTHS = dict(hidden_size=256, mamba_n_heads=8, mamba_d_head=64,
                     mamba_d_state=128, max_position_embeddings=256)


@pytest.fixture()
def ssd_kernels_taken(monkeypatch):
    """What `transformer._scan` does on a TPU: the kernels wherever their
    rule admits the call (interpreted here). -> the chunks they ran at."""
    seen = []
    scan = ssd_kernel.ssd

    def noting(x, dt, acs, Bm, Cm, chunk):
        seen.append(chunk)
        return scan(x, dt, acs, Bm, Cm, chunk)

    monkeypatch.setattr(ssd_kernel, "_on_tpu", lambda: True)
    monkeypatch.setattr(ssd_kernel, "ssd", noting)
    return seen


@pytest.mark.parametrize("order,chunk,impl", [
    *((order, chunk, "einsums") for chunk in (T, T // 2, T // 8)
      for order in sorted(ORDERS)),
    ("mamba_first", 128, "kernels"), ("alternating", 128, "kernels")])
def test_chunked_form_is_the_recurrence_over_time(order, chunk, impl,
                                                  request):
    """The SSD form at 1, 2 and 8 chunks a sequence against the reference's
    scan over time, in stacks whose runs differ: loss within 1e-6, final
    hidden state within 1e-5 of its RMS (float32 both, summation order).
    Both implementations of it: `_ssd`'s einsums at the toy widths, and the
    kernels of `kernels/ssd.py` at the narrowest widths they slice, two
    chunks of 128 (there dt rides in the decay's exponent: 2e-5)."""
    taken = (request.getfixturevalue("ssd_kernels_taken")
             if impl == "kernels" else None)
    hf = {**HF, **(KERNEL_WIDTHS if taken is not None else {}),
          "layer_types": ORDERS[order], "mamba_chunk_size": chunk}
    cfg, params = _seeded(hf, 6)
    sd = hf_granite.state_dict_from_params(params, cfg)
    tokens, targets = _data(hf["vocab_size"], 7,
                            seq=T if taken is None else 2 * chunk)
    want_loss, want = reference.loss_terms(sd, tokens, targets, hf)
    with jax.default_matmul_precision("highest"):
        loss = tfm.loss_fn(params, tokens, targets, cfg)
        h, _ = tfm.forward_hidden(params, tokens, cfg)
    assert abs(float(loss) - float(want_loss)) < 1e-6
    assert rel(h, want["hidden"][-1]) < (1e-5 if taken is None else 2e-5)
    assert taken is None or (taken and set(taken) == {chunk})


def test_two_groups_share_b_and_c_by_group():
    """`mamba_n_groups` 2: heads 0-3 read group 0's B and C, heads 4-7 group
    1's, in the system's (G, H/G) einsums as in the reference's repeat."""
    hf = {**HF, "mamba_n_groups": 2}
    cfg, params = _seeded(hf, 8)
    sd = hf_granite.state_dict_from_params(params, cfg)
    tokens, targets = _data(hf["vocab_size"], 9)
    want_loss, _ = reference.loss_terms(sd, tokens, targets, hf)
    with jax.default_matmul_precision("highest"):
        loss = tfm.loss_fn(params, tokens, targets, cfg)
    assert abs(float(loss) - float(want_loss)) < 1e-6


def test_fused_ce_and_bf16_paths_run_the_hybrid_stack():
    """The step as the chip takes it (bf16 compute, the fused CE on the tied
    "vd" head with logits / 8 folded into its rows, remat) against the
    float32 reference: bf16's 8 bits of mantissa through 4 layers, loss
    within 2e-2 of ~5.5 (measured 3e-3)."""
    cfg, params = _seeded(HF, 10)
    cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16, fused_lm_ce=True)
    sd = hf_granite.state_dict_from_params(params, cfg)
    tokens, targets = _data(HF["vocab_size"], 11)
    want_loss, _ = reference.loss_terms(sd, tokens, targets, HF)
    step = tfm.make_train_step(cfg, lr=1e-3)
    loss, new_params, _ = step(params, tfm.init_opt_state(params), tokens,
                               targets)
    assert abs(float(loss) - float(want_loss)) < 2e-2
    assert jax.tree.structure(new_params) == jax.tree.structure(params)
    assert all(np.all(np.isfinite(np.asarray(x)))
               for x in jax.tree.leaves(new_params))


# -- the refactor's contract -----------------------------------------------------

OLMOE_HF = dict(
    vocab_size=512, hidden_size=128, intermediate_size=64,
    num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=2,
    num_experts=8, num_experts_per_tok=2, norm_topk_prob=False,
    max_position_embeddings=32, rope_theta=10000.0, rms_norm_eps=1e-5,
    hidden_act="silu", tie_word_embeddings=False, router_aux_loss_coef=0.01)
OURO_HF = dict(
    head_dim=32, hidden_act="silu", hidden_size=64, intermediate_size=128,
    max_position_embeddings=32, num_attention_heads=2, num_hidden_layers=2,
    num_key_value_heads=2, rms_norm_eps=1e-6, rope_theta=10000.0,
    tie_word_embeddings=False, total_ut_steps=3, vocab_size=256,
    layer_types=["full_attention"] * 2)


def _homogeneous(which):
    if which == "bert":
        return bert.BertConfig.hf(
            vocab_size=256, d_model=64, n_layers=2, n_heads=4, d_ff=128,
            max_seq_len=32, dtype=jnp.float32).trunk()
    if which == "olmoe":
        return hf_olmoe.config_from_hf(OLMOE_HF)
    return hf_ouro.config_from_hf(OURO_HF)


@pytest.mark.parametrize("which", ["bert", "olmoe", "ouro"])
def test_all_attention_layer_types_are_the_homogeneous_stack(which):
    """`layer_types=()` and `("attention",) * L`: the same parameter pytree
    with the same bits from one key, the same PartitionSpecs, and the same
    loss and gradients to the bit (both jitted: one program twice)."""
    cfg = _homogeneous(which)
    named = dataclasses.replace(
        cfg, layer_types=("attention",) * cfg.n_layers)
    key = jax.random.PRNGKey(12)
    a, b = tfm.init_params(key, cfg), tfm.init_params(key, named)
    assert jax.tree.structure(a) == jax.tree.structure(b)
    assert isinstance(a["blocks"], dict)
    assert all(np.array_equal(x, y) for x, y in zip(jax.tree.leaves(a),
                                                    jax.tree.leaves(b)))
    assert tfm.param_specs(cfg) == tfm.param_specs(named)
    tokens, targets = _data(cfg.vocab_size, 13)
    fn = lambda c: jax.jit(jax.value_and_grad(
        lambda p: tfm.loss_fn(p, tokens, targets, c)))(a)
    (la, ga), (lb, gb) = fn(cfg), fn(named)
    assert float(la) == float(lb)
    assert all(np.array_equal(x, y) for x, y in zip(jax.tree.leaves(ga),
                                                    jax.tree.leaves(gb)))


def test_hybrid_pytree_is_one_stacked_dict_a_run():
    cfg, params = _seeded(HF, 14)
    assert tfm.layer_runs(cfg) == (("mamba", 2), ("attention", 1),
                                   ("mamba", 1))
    blocks = params["blocks"]
    assert isinstance(blocks, tuple) and len(blocks) == 3
    assert blocks[0]["w_in"].shape[0] == 2 and "wqkv" not in blocks[0]
    assert blocks[1]["wqkv"].shape[0] == 1 and "w_in" not in blocks[1]
    specs = tfm.param_specs(cfg)
    assert jax.tree.structure(params) == jax.tree.structure(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    # the two later runs do not repeat run 0's draws
    assert not np.array_equal(blocks[0]["w_in"][0], blocks[2]["w_in"][0])
    assert not np.array_equal(blocks[0]["w1"][0], blocks[1]["w1"][0])


def test_benchmark_config_gives_the_published_widths_and_647m():
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        config = json.load(f)
    assert len(config["layer_types"]) == 40
    assert [i for i, k in enumerate(config["layer_types"])
            if k == "attention"] == [5, 15, 25, 35]
    cfg = hf_granite.config_from_hf(config, dtype=jnp.bfloat16)
    assert cfg.layer_types == ("mamba",) * 5 + ("attention",)
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.d_ff,
            cfg.vocab_size, cfg.max_seq_len) == (2048, 32, 8, 64, 8192,
                                                 100352, 131072)
    assert cfg.ssm == tfm.SSMConfig(n_heads=64, head_dim=64, d_state=128,
                                    n_groups=1, d_conv=4, chunk=256)
    assert (cfg.ssm.d_inner, cfg.ssm.conv_dim) == (4096, 4352)
    assert cfg.multipliers == tfm.Multipliers(
        embedding=12.0, residual=0.22, attention=0.015625, logits=8.0)
    assert cfg.tied_head and not cfg.rope and not cfg.use_pos_emb
    assert cfg.norm == "rmsnorm" and cfg.ln_eps == 1e-5 and cfg.causal
    shapes = jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    # the bias leaves the rmsnorm / swiglu dialect carries and ignores
    ignored = 2048 + sum(
        int(np.prod(v.shape)) for b in shapes["blocks"]
        for k, v in b.items() if k in ("ln1_bias", "ln2_bias", "b1", "b2"))
    assert tfm.count_params(shapes) - ignored == 647_259_328
    assert shapes["blocks"][0]["w_in"].shape == (5, 2048, 4096 + 4352 + 64)


# -- refusals by name ------------------------------------------------------------

def test_sequence_that_is_no_multiple_of_the_chunk_is_refused_by_name():
    cfg, params = _seeded(HF, 15)
    tokens, targets = _data(HF["vocab_size"], 16, seq=T - 4)
    with pytest.raises(ValueError, match=r"seq_len=28.*chunk=8"):
        tfm.loss_fn(params, tokens, targets, cfg)


def test_generate_and_pipeline_refuse_mamba_layers_by_name():
    from hetu_tpu.models import generate
    from hetu_tpu.parallel import pipeline
    cfg, _ = _seeded(HF, 17)
    refuses(lambda: generate._check_decode_args(cfg, 16, 0),
            "layer_types=('mamba', 'mamba', 'attention', 'mamba')")
    with pytest.raises(NotImplementedError, match="mamba"):
        pipeline._make_stage_fn(cfg, 2)


def test_config_refuses_what_the_trunk_does_not_run():
    with pytest.raises(NotImplementedError, match="num_local_experts"):
        hf_granite.config_from_hf({**HF, "num_local_experts": 8})
    with pytest.raises(NotImplementedError, match="layer_types"):
        hf_granite.config_from_hf({**HF, "layer_types": ["mamba", "swa",
                                                         "mamba", "mamba"]})
    with pytest.raises(ValueError, match="layer_types"):
        tfm.TransformerConfig(n_layers=2, layer_types=("mamba", "mamba"))


# -- names and bytes ---------------------------------------------------------------

def test_ssm_scopes_in_the_compiled_program_forward_and_backward():
    """The four `hetu_ssm_*` scopes in the `op_name` paths of the compiled
    step, under the forward and the transposed phase (what
    benchmark/reduce/ssm.py reads), none under the optimizer."""
    cfg, params = _seeded(HF, 18)
    tokens, targets = _data(HF["vocab_size"], 19)
    text = tfm.make_train_step(cfg).lower(
        params, tfm.init_opt_state(params), tokens,
        targets).compile().as_text()
    names = set(re.findall(r'op_name="([^"]+)"', text))
    for scope in tracing.SSM_SCOPES:
        under = [n for n in names if f"/{scope}/" in n]
        assert any(f"/jvp({tracing.SCOPE_FWD})/" in n for n in under), scope
        assert any(f"/transpose(jvp({tracing.SCOPE_FWD}))/" in n
                   for n in under), scope
        assert not [n for n in under if tracing.SCOPE_OPT in n], scope


def test_remat_counts_bytes_by_kind(monkeypatch):
    """x1 and x2 cost every layer's (B, T, D); o, lse, q, k and v the ONE
    attention layer's; the working set is the larger kind's (the Mamba
    block's)."""
    cfg, params = _seeded(HF, 20)
    cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    h = jax.ShapeDtypeStruct((2, T, 64), jnp.bfloat16)
    runs = dict(zip(tfm.layer_runs(cfg)[:2],
                    tfm.run_blocks(cfg, params["blocks"])))
    by_kind = {kind: tfm._block_residual_bytes(cfg, None, h, b, None, kind)
               for (kind, _), b in runs.items()}
    assert by_kind["mamba"] > by_kind["attention"] > 0
    act, lse = 2 * T * 64 * 2, 2 * T * 4 * 4
    state = tfm._state_bytes(cfg, params, None)
    limit = int((state + 4 * act + by_kind["mamba"] + 10 * act) * 32 / 31) + 64
    names, held, budget = tfm._remat_names(cfg, params, h, None,
                                           bytes_limit=limit)
    # and its q with k and v at 2 of 4 heads (the split path: grouped
    # heads and a multiplier); no sandwich norm, so the last group is skipped
    assert names == sum(tracing.REMAT_CANDIDATES[:3], ())
    assert held == 4 * act + 1 * (act + lse) + 1 * (act + 2 * act * 2 // 4)
    names, held, _ = tfm._remat_names(
        cfg, params, h, None, bytes_limit=limit - 10 * act + 4 * act + 8)
    assert names == tracing.REMAT_CANDIDATES[0] and held == 4 * act
