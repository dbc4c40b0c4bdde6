"""KV-cache decode correctness: teacher-forced incremental logits must
equal the full training forward's logits position by position (the cache
path and the batch path are the same function or one of them is wrong),
plus greedy self-consistency and sampling-shape checks.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from hetu_tpu.models import generate as gen
from hetu_tpu.models import transformer as tfm

CFG = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_layers=3, d_ff=64, max_seq_len=16,
                            dtype=jnp.float32, remat=False)


def test_incremental_logits_match_full_forward():
    params = tfm.init_params(jax.random.PRNGKey(0), CFG)
    rng = np.random.RandomState(0)
    prompt = jnp.asarray(rng.randint(0, CFG.vocab_size, (2, 16)), jnp.int32)

    full_logits, _ = tfm.forward(params, prompt, CFG)          # (B, T, V)
    fn = gen.make_generate_fn(CFG, max_len=16)
    toks, inc_logits = fn(params, prompt, jax.random.PRNGKey(1))

    np.testing.assert_array_equal(np.asarray(toks), np.asarray(prompt))
    np.testing.assert_allclose(np.asarray(inc_logits),
                               np.asarray(full_logits), atol=2e-4)


def _spec_cfgs():
    target = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                   n_layers=3, d_ff=64, max_seq_len=40,
                                   dtype=jnp.float32, remat=False)
    draft = tfm.TransformerConfig(vocab_size=64, d_model=16, n_heads=2,
                                  n_layers=1, d_ff=32, max_seq_len=40,
                                  dtype=jnp.float32, remat=False)
    return target, draft


@pytest.mark.parametrize("k,P", [(1, 3), (3, 5), (4, 1), (6, 9)])
def test_speculative_equals_plain_greedy(k, P):
    """The exactness contract: speculative output == plain greedy decode
    with the target, for any draft — here an unrelated random model, so
    rejections happen constantly."""
    target, draft = _spec_cfgs()
    tp = tfm.init_params(jax.random.PRNGKey(0), target)
    dp = tfm.init_params(jax.random.PRNGKey(99), draft)
    rng = np.random.RandomState(P * 7 + k)
    prompt = jnp.asarray(rng.randint(0, 64, (1, P)), jnp.int32)
    max_len = 24
    plain = gen.generate(tp, target, np.asarray(prompt), max_len=max_len)
    fn = gen.make_speculative_generate_fn(target, draft, max_len, k=k)
    spec, rounds = fn(tp, dp, prompt)
    np.testing.assert_array_equal(np.asarray(spec), plain)
    assert int(rounds) >= 1


def test_speculative_self_draft_accepts_everything():
    """draft == target: every proposal is accepted, so the loop advances
    k+1 tokens per round — rounds == ceil(generated / (k+1))."""
    target, _ = _spec_cfgs()
    tp = tfm.init_params(jax.random.PRNGKey(1), target)
    P, max_len, k = 4, 25, 4
    prompt = jnp.asarray(
        np.random.RandomState(0).randint(0, 64, (1, P)), jnp.int32)
    fn = gen.make_speculative_generate_fn(target, target, max_len, k=k)
    spec, rounds = fn(tp, tp, prompt)
    plain = gen.generate(tp, target, np.asarray(prompt), max_len=max_len)
    np.testing.assert_array_equal(np.asarray(spec), plain)
    generated_after_prefill = max_len - P - 1
    assert int(rounds) == -(-generated_after_prefill // (k + 1))


def test_chunked_prefill_matches_tokenwise():
    """_chunk_logits over a whole prompt equals the token-by-token cache
    build (the chunked path is new; the scan path is the oracle)."""
    cfg, _ = _spec_cfgs()
    params = tfm.init_params(jax.random.PRNGKey(2), cfg)
    rng = np.random.RandomState(3)
    toks = jnp.asarray(rng.randint(0, 64, (2, 9)), jnp.int32)
    L, B, nh, hd, M = cfg.n_layers, 2, cfg.n_heads, cfg.head_dim, 16
    kc = jnp.zeros((L, B, nh, M, hd), cfg.dtype)
    vc = jnp.zeros_like(kc)
    chunk_logits, kc_c, vc_c = gen._chunk_logits(params, cfg, toks,
                                                 kc, vc, 0)
    kc2, vc2 = jnp.zeros_like(kc), jnp.zeros_like(vc)
    steps = []
    for t in range(9):
        lg, kc2, vc2 = gen._one_token_logits(params, cfg, toks[:, t],
                                             kc2, vc2, t)
        steps.append(lg)
    np.testing.assert_allclose(np.asarray(chunk_logits),
                               np.stack([np.asarray(s) for s in steps], 1),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(kc_c), np.asarray(kc2),
                               atol=2e-6, rtol=2e-6)


def test_incremental_logits_match_forward_postln_bias_dialect():
    """The decode path must honor the canonical-architecture knobs
    (post-LN blocks, projection biases, non-default LN eps, erf gelu) —
    a config trained with them must decode through the SAME network."""
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                n_layers=3, d_ff=64, max_seq_len=16,
                                dtype=jnp.float32, remat=False,
                                post_ln=True, attn_proj_bias=True,
                                ln_eps=1e-12, gelu_exact=True)
    params = tfm.init_params(jax.random.PRNGKey(5), cfg)
    # non-zero biases so a dropped bias add would be caught
    params["blocks"]["bqkv"] = jax.random.normal(
        jax.random.PRNGKey(6), params["blocks"]["bqkv"].shape) * 0.1
    params["blocks"]["bo"] = jax.random.normal(
        jax.random.PRNGKey(7), params["blocks"]["bo"].shape) * 0.1
    rng = np.random.RandomState(2)
    prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 16)), jnp.int32)
    full_logits, _ = tfm.forward(params, prompt, cfg)
    fn = gen.make_generate_fn(cfg, max_len=16)
    toks, inc_logits = fn(params, prompt, jax.random.PRNGKey(8))
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(prompt))
    np.testing.assert_allclose(np.asarray(inc_logits),
                               np.asarray(full_logits), atol=2e-4)


def test_greedy_continuation_is_self_consistent():
    """Greedy tokens re-fed through the full forward must be argmax-stable:
    feeding the generated sequence reproduces its own continuations."""
    params = tfm.init_params(jax.random.PRNGKey(2), CFG)
    rng = np.random.RandomState(1)
    prompt = rng.randint(0, CFG.vocab_size, (3, 4)).astype(np.int32)
    out = gen.generate(params, CFG, prompt, max_len=12)
    assert out.shape == (3, 12)
    np.testing.assert_array_equal(out[:, :4], prompt)

    logits, _ = tfm.forward(params, jnp.asarray(out), CFG)
    pred = np.argmax(np.asarray(logits), -1)
    # positions 4..11 were generated greedily from the prefix
    np.testing.assert_array_equal(out[:, 4:], pred[:, 3:11])


def test_temperature_sampling_shapes_and_determinism():
    params = tfm.init_params(jax.random.PRNGKey(3), CFG)
    prompt = np.zeros((2, 2), np.int32)
    a = gen.generate(params, CFG, prompt, max_len=8, temperature=1.0,
                     rng=jax.random.PRNGKey(7))
    b = gen.generate(params, CFG, prompt, max_len=8, temperature=1.0,
                     rng=jax.random.PRNGKey(7))
    c = gen.generate(params, CFG, prompt, max_len=8, temperature=1.0,
                     rng=jax.random.PRNGKey(8))
    assert a.shape == (2, 8)
    np.testing.assert_array_equal(a, b)      # same key -> same sample
    assert (a != c).any()                    # different key -> different


def test_top_k_sampling_restricts_support():
    """top_k=1 sampling must equal greedy decoding exactly."""
    params = tfm.init_params(jax.random.PRNGKey(4), CFG)
    prompt = np.zeros((2, 2), np.int32)
    fn_k1 = gen.make_generate_fn(CFG, max_len=10, sample=True, top_k=1)
    toks_k1, _ = fn_k1(params, jnp.asarray(prompt), jax.random.PRNGKey(0),
                       1.0)
    greedy = gen.generate(params, CFG, prompt, max_len=10)
    np.testing.assert_array_equal(np.asarray(toks_k1), greedy)


def test_ragged_prompts_match_per_row_decode():
    """prompt_lens decodes a ragged batch in ONE call: each row must be
    token-exact vs decoding that row alone with its true length (greedy),
    for both the scan and the EOS while_loop paths."""
    params = tfm.init_params(jax.random.PRNGKey(2), CFG)
    rng = np.random.RandomState(4)
    lens = [3, 7, 5]
    Pmax, M = max(lens), 12
    prompt = np.zeros((len(lens), Pmax), np.int32)
    for b, ln in enumerate(lens):
        prompt[b, :ln] = rng.randint(1, CFG.vocab_size, ln)
    prompt = jnp.asarray(prompt)

    fn = gen.make_generate_fn(CFG, max_len=M)
    toks, _ = fn(params, prompt, jax.random.PRNGKey(0),
                 prompt_lens=jnp.asarray(lens, jnp.int32))
    for b, ln in enumerate(lens):
        # solo rows pass prompt_lens too: the exactness guarantee is
        # scoped to the SAME prefill mechanism (the ragged batch
        # teacher-forces in-loop; a bare rectangular call would use the
        # chunked prefill, whose tilings may tie-break differently)
        solo, _ = fn(params, prompt[b:b + 1, :ln], jax.random.PRNGKey(0),
                     prompt_lens=jnp.asarray([ln], jnp.int32))
        np.testing.assert_array_equal(np.asarray(toks[b]),
                                      np.asarray(solo[0]),
                                      err_msg=f"row {b} (len {ln})")
        # on the CPU test backend the chunked prefill is additionally
        # bit-identical to the tokenwise path (TPU tilings may not be)
        chunked, _ = fn(params, prompt[b:b + 1, :ln], jax.random.PRNGKey(0))
        np.testing.assert_array_equal(np.asarray(chunked[0]),
                                      np.asarray(solo[0]))

    # EOS path: same ragged semantics (greedy rows match the scan path up
    # to each row's first eos; after it the tail is eos-filled)
    eos = int(np.asarray(toks[0, lens[0]]))  # a token row 0 actually emits
    efn = gen.make_eos_generate_fn(CFG, max_len=M, eos_id=eos)
    etoks, _ = efn(params, prompt, jax.random.PRNGKey(0),
                   prompt_lens=jnp.asarray(lens, jnp.int32))
    for b, ln in enumerate(lens):
        row, erow = np.asarray(toks[b]), np.asarray(etoks[b])
        gen_slice = slice(ln, M)
        first_eos = np.where(row[gen_slice] == eos)[0]
        stop = (ln + int(first_eos[0]) + 1) if len(first_eos) else M
        np.testing.assert_array_equal(erow[:stop], row[:stop],
                                      err_msg=f"row {b}")
        assert np.all(erow[stop:] == eos), erow


def test_tp_sharded_decode_matches_single_device():
    """Greedy decode on a dp2 x tp2 mesh: params stay Megatron-sharded, the
    KV cache is dp/tp-sharded, tokens match the unsharded decode exactly."""
    from hetu_tpu.parallel.mesh import auto_mesh

    mesh = auto_mesh(8, tp=2)
    params = tfm.init_params(jax.random.PRNGKey(5), CFG)
    rng = np.random.RandomState(2)
    prompt = rng.randint(0, CFG.vocab_size, (4, 4)).astype(np.int32)

    ref = gen.generate(params, CFG, prompt, max_len=12)

    sharded = tfm.shard_params(params, CFG, mesh)
    fn = gen.make_generate_fn(CFG, max_len=12, mesh=mesh)
    toks, _ = fn(sharded, jnp.asarray(prompt), jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(toks), ref)
    # the weights really stayed distributed through decode: the Megatron
    # layout holds shards on multiple devices (not GSPMD-replicated away)
    wqkv = sharded["blocks"]["wqkv"]
    assert len({s.device for s in wqkv.addressable_shards}) == 8


def test_mqa_sharded_decode_replicates_undivisible_kv_heads():
    """MQA (1 kv head) under tp=2: the cache stores nkv UNBROADCAST heads,
    which tp cannot divide — the head axis must fall back to replication
    (regression guard for the round-5 GQA cache change) while tokens still
    match the unsharded decode."""
    from hetu_tpu.parallel.mesh import auto_mesh

    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                n_kv_heads=1, n_layers=2, d_ff=64,
                                max_seq_len=16, dtype=jnp.float32,
                                remat=False)
    mesh = auto_mesh(8, tp=2)
    params = tfm.init_params(jax.random.PRNGKey(7), cfg)
    rng = np.random.RandomState(4)
    prompt = rng.randint(0, cfg.vocab_size, (4, 4)).astype(np.int32)

    ref = gen.generate(params, cfg, prompt, max_len=12)
    sharded = tfm.shard_params(params, cfg, mesh)
    fn = gen.make_generate_fn(cfg, max_len=12, mesh=mesh)
    toks, _ = fn(sharded, jnp.asarray(prompt), jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(toks), ref)


def test_beam_size_one_equals_greedy():
    params = tfm.init_params(jax.random.PRNGKey(6), CFG)
    rng = np.random.RandomState(3)
    prompt = rng.randint(0, CFG.vocab_size, (3, 3)).astype(np.int32)
    greedy = gen.generate(params, CFG, prompt, max_len=10)
    fn = gen.make_beam_search_fn(CFG, max_len=10, beam_size=1)
    toks, scores = fn(params, jnp.asarray(prompt))
    np.testing.assert_array_equal(np.asarray(toks[:, 0]), greedy)
    assert np.all(np.isfinite(np.asarray(scores[:, 0])))


def test_beam_search_finds_global_optimum_when_exhaustive():
    """With beam_size >= V^(n_generated), beam search IS exhaustive search:
    its best sequence must equal the brute-force argmax over all
    continuations scored by the full forward."""
    cfg = tfm.TransformerConfig(vocab_size=5, d_model=16, n_heads=2,
                                n_layers=2, d_ff=32, max_seq_len=8,
                                dtype=jnp.float32, remat=False)
    params = tfm.init_params(jax.random.PRNGKey(7), cfg)
    prompt = np.array([[1, 2]], np.int32)
    P, M, V = 2, 4, 5                       # generate 2 tokens -> 25 seqs

    fn = gen.make_beam_search_fn(cfg, max_len=M, beam_size=V * V)
    toks, scores = fn(params, jnp.asarray(prompt))

    # brute force: score every continuation with the full forward
    best, best_score = None, -np.inf
    for a in range(V):
        for b in range(V):
            seq = np.array([[1, 2, a, b]], np.int32)
            logits, _ = tfm.forward(params, jnp.asarray(seq), cfg)
            lp = np.asarray(jax.nn.log_softmax(
                np.asarray(logits, np.float64), -1))
            s = lp[0, 1, a] + lp[0, 2, b]   # logp of a after pos1, b after 2
            if s > best_score:
                best, best_score = (a, b), s
    assert tuple(np.asarray(toks[0, 0, P:])) == best
    assert float(scores[0, 0]) == pytest.approx(best_score, abs=1e-3)


def test_beam_scores_are_consistent_and_sorted():
    """Each returned beam's score must equal the forward-recomputed
    log-probability of its own generated suffix, and beams come back
    best-first. (A wider beam is NOT guaranteed to beat greedy — beam
    search can prune the greedy path — so that is deliberately not
    asserted.)"""
    params = tfm.init_params(jax.random.PRNGKey(8), CFG)
    rng = np.random.RandomState(4)
    prompt = rng.randint(0, CFG.vocab_size, (2, 3)).astype(np.int32)
    P, M = 3, 9
    fn = gen.make_beam_search_fn(CFG, max_len=M, beam_size=4)
    toks, scores = fn(params, jnp.asarray(prompt))
    s = np.asarray(scores)
    assert np.all(s[:, :-1] >= s[:, 1:] - 1e-6)   # sorted best-first
    for b in range(2):
        for k in range(4):
            seq = np.asarray(toks[b, k])[None]
            logits, _ = tfm.forward(params, jnp.asarray(seq), CFG)
            lp = np.asarray(jax.nn.log_softmax(
                np.asarray(logits, np.float64), -1))
            want = sum(lp[0, t - 1, seq[0, t]] for t in range(P, M))
            assert s[b, k] == pytest.approx(want, abs=1e-3), (b, k)


def test_eos_decode_matches_scan_and_exits_early():
    """EOS while_loop decode must equal the fixed-length scan decode up to
    each row's first generated EOS (then pad with EOS), and must execute
    FEWER steps than max_len when every row finishes early."""
    params = tfm.init_params(jax.random.PRNGKey(9), CFG)
    rng = np.random.RandomState(5)
    prompt = rng.randint(0, CFG.vocab_size, (4, 3)).astype(np.int32)
    M = 16

    full = gen.generate(params, CFG, prompt, max_len=M)   # greedy scan
    # choose as EOS the most common token greedy emits -> early finishes
    gen_part = full[:, 3:]
    eos = int(np.bincount(gen_part.ravel()).argmax())

    fn = gen.make_eos_generate_fn(CFG, max_len=M, eos_id=eos)
    toks, steps = fn(params, jnp.asarray(prompt), jax.random.PRNGKey(0))
    toks = np.asarray(toks)

    for b in range(4):
        row_full = full[b]
        hit = np.where(row_full[3:] == eos)[0]
        end = (3 + hit[0] + 1) if len(hit) else M
        np.testing.assert_array_equal(toks[b, :end], row_full[:end])
        assert np.all(toks[b, end:] == eos)
    if all(np.any(full[b, 3:] == eos) for b in range(4)):
        last_eos = max((3 + np.where(full[b, 3:] == eos)[0][0])
                       for b in range(4))
        assert int(steps) <= last_eos + 1 < M   # genuinely exited early


def test_a_field_the_block_does_not_mirror_is_refused_by_name():
    """The rule, not a list: `conv_width` is no model's mixer and no assert
    ever named it; off its default it is refused with its value, and
    `layer_types` spelled out as attention throughout is the plain block."""
    import dataclasses
    with pytest.raises(AssertionError, match=r"plain attention block.*"
                                             r"conv_width=4"):
        gen._check_decode_args(dataclasses.replace(CFG, conv_width=4), 16, 0)
    gen._check_decode_args(dataclasses.replace(
        CFG, layer_types=("attention",) * CFG.n_layers), 16, 0)
