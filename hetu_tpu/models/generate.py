"""Autoregressive generation with a KV cache — TPU-idiomatic decode.

The reference framework stops at training + an inference subexecutor that
re-runs the full forward; it has no incremental decoding. For an LM
framework that is half the user surface, so this module adds it the TPU
way: the whole generate loop is ONE ``lax.scan`` over time steps (static
shapes, no retrace, no host round-trips), each step updating a
(L, B, n_kv_heads, max_len, hd) key/value cache via ``dynamic_update_slice``
(GQA checkpoints keep their kv-cache memory saving at serving time) and
scanning the layer stack exactly like training does
(``models/transformer.py`` keeps per-layer params stacked on a leading L
axis).

Prompt handling: rectangular prompts prefill positions [0, P-1) in ONE
chunked forward (an MXU-shaped matmul; see ``_chunk_hidden``), then the
scan/while loop decodes from the boundary; ragged batches (per-row
``prompt_lens``) teacher-force inside the loop instead, since each row
crosses its own prompt boundary at a different step. Either way the whole
thing is one compiled program.

The plain attention block only: ``_check_decode_args`` lists the config
fields the mirrored block reads (``_MIRRORED``) and refuses, by name, every
other field that is off its default: experts, other mixers, loops, a share of
an expert layer, and whatever a later model adds. Decode runs single-program
(``mesh=None``) or distributed: with a mesh, params keep their Megatron tp
layout, the KV cache shards batch-over-dp and heads-over-tp, and GSPMD
inserts the collectives (see ``make_generate_fn``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from . import transformer as tfm


def _decode_layer(carry, layer_inputs, *, cfg, pos):
    """One transformer block for a CHUNK of C new tokens against the cache
    (C=1 is the classic decode step; C>1 is chunk verification for
    speculative decoding — attention is causal WITHIN the chunk and full
    over the cached prefix).

    carry: h (B, C, D); layer_inputs: (layer_params, k_cache, v_cache) with
    caches (B, nkv, M, hd); the chunk occupies positions [pos, pos+C).
    Returns updated caches alongside the new h.

    LOCKSTEP CONTRACT with ``transformer._block``: every architecture
    dialect knob (post_ln, attn_proj_bias, ln_eps, gelu flavor, future
    additions) must behave identically here, or decode silently runs a
    different network than training —
    test_incremental_logits_match_forward_postln_bias_dialect pins the
    current knob set.
    """
    h = carry
    p, kc, vc = layer_inputs
    B, C, D = h.shape
    nh, hd = cfg.n_heads, cfg.head_dim
    nkv = cfg.kv_heads
    M = kc.shape[2]

    post = cfg.post_ln
    attn_in = h if post else tfm._norm(h, p["ln1_scale"],
                                       p["ln1_bias"], cfg)
    qkv = jnp.einsum("bod,de->boe", attn_in, p["wqkv"].astype(h.dtype),
                     preferred_element_type=jnp.float32).astype(h.dtype)
    if cfg.attn_proj_bias:
        qkv = qkv + p["bqkv"].astype(h.dtype)
    q, k, v = jnp.split(qkv, [nh * hd, (nh + nkv) * hd], axis=-1)
    if cfg.rope:
        # rotate at the chunk's absolute positions; the cache stores
        # ROTATED keys (scores are position-relative after rotation)
        q = tfm._rope(q, pos, cfg.rope_theta, hd)
        k = tfm._rope(k, pos, cfg.rope_theta, hd)
    q = q.reshape(B, C, nh, hd).transpose(0, 2, 1, 3)   # (B, nh, C, hd)
    k = k.reshape(B, C, nkv, hd).transpose(0, 2, 1, 3)
    v = v.reshape(B, C, nkv, hd).transpose(0, 2, 1, 3)
    # gqa: the cache stores the nkv UNBROADCAST heads — the memory saving
    # is the point of a GQA checkpoint at serving time — and the scores
    # ride a grouped einsum (g query heads share each kv head); g=1
    # degenerates to classic MHA with identical math
    kc = jax.lax.dynamic_update_slice(kc, k, (0, 0, pos, 0))
    vc = jax.lax.dynamic_update_slice(vc, v, (0, 0, pos, 0))

    g = nh // nkv
    qg = q.reshape(B, nkv, g, C, hd)
    scores = jnp.einsum("bngqd,bnkd->bngqk", qg, kc,
                        preferred_element_type=jnp.float32) / np.sqrt(hd)
    # query i (global position pos+i) sees cache entries <= pos+i
    kpos = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, C, M), 4)
    qpos = pos + jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, C, M), 3)
    scores = jnp.where(kpos <= qpos, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(h.dtype)
    ctx = jnp.einsum("bngqk,bnkd->bngqd", probs, vc,
                     preferred_element_type=jnp.float32).astype(h.dtype)
    ctx = ctx.reshape(B, nh, C, hd).transpose(0, 2, 1, 3).reshape(B, C, D)
    attn_out = jnp.einsum("bod,de->boe", ctx, p["wo"].astype(h.dtype),
                          preferred_element_type=jnp.float32).astype(h.dtype)
    if cfg.attn_proj_bias:
        attn_out = attn_out + p["bo"].astype(h.dtype)
    h = h + attn_out
    if post:
        h = tfm._norm(h, p["ln1_scale"], p["ln1_bias"], cfg)

    mlp_in = h if post else tfm._norm(h, p["ln2_scale"],
                                      p["ln2_bias"], cfg)
    h = h + tfm._dense_mlp(mlp_in, p, cfg, None)
    if post:
        h = tfm._norm(h, p["ln2_scale"], p["ln2_bias"], cfg)
    return h, (kc, vc)


def _chunk_hidden(params, cfg, toks, kcache, vcache, pos):
    """toks (B, C) int32 occupying positions [pos, pos+C) -> (hidden
    (B, C, D) pre-head, new caches). The cache-building core; callers that
    need logits apply ``tfm.lm_head`` to as little of h as they actually
    read (at V~50k the head dominates, so prefill must not pay it for
    every prompt position)."""
    B, C = toks.shape
    D = cfg.d_model
    h = params["embed"][toks].astype(cfg.dtype)
    if cfg.use_pos_emb:
        pos_emb = jax.lax.dynamic_slice(params["pos"], (pos, 0), (C, D))
        h = h + pos_emb[None].astype(cfg.dtype)
    h, (kcache, vcache) = jax.lax.scan(
        functools.partial(_decode_layer, cfg=cfg, pos=pos), h,
        (params["blocks"], kcache, vcache))
    return h, kcache, vcache


def _chunk_logits(params, cfg, toks, kcache, vcache, pos):
    """toks (B, C) int32 occupying positions [pos, pos+C) -> (logits
    (B, C, V), new caches). C=1 is one decode step."""
    h, kcache, vcache = _chunk_hidden(params, cfg, toks, kcache, vcache,
                                      pos)
    return tfm.lm_head(params, h, cfg), kcache, vcache


def _one_token_logits(params, cfg, tok, kcache, vcache, pos):
    """tok (B,) int32 at position pos -> (logits (B, V), new caches)."""
    logits, kcache, vcache = _chunk_logits(params, cfg, tok[:, None],
                                           kcache, vcache, pos)
    return logits[:, 0], kcache, vcache


def _prefill_prefix(params, cfg, prompt, kcache, vcache, enabled,
                    prompt_lens, want_logits):
    """Shared rectangular-prompt prefill: when ``enabled`` and the batch is
    rectangular (``prompt_lens is None``), positions [0, P-1) run as ONE
    chunked forward. Returns (start, prefix_logits, kcache, vcache) —
    start is the loop's first step (P-1, or 0 when prefill did not apply);
    prefix_logits is the (B, P-1, V) head output when ``want_logits``
    (callers whose contract returns per-position logits), else None."""
    P = prompt.shape[1]
    if not (enabled and prompt_lens is None and P > 1):
        return 0, None, kcache, vcache
    h, kcache, vcache = _chunk_hidden(params, cfg, prompt[:, :P - 1],
                                      kcache, vcache, 0)
    prefix = tfm.lm_head(params, h, cfg) if want_logits else None
    return P - 1, prefix, kcache, vcache


# The ``TransformerConfig`` fields the mirrored block READS (``_decode_layer``,
# ``_chunk_hidden``, and ``tfm._norm`` / ``_rope`` / ``_dense_mlp`` /
# ``lm_head`` under them; ``causal`` has a check of its own), beside the
# knobs of the TRAINING step alone, which no decode program sees
# (``attn_impl``, ``fused_lm_ce``, ``remat``, ``dropout_rate``). Every OTHER
# field says "not the plain attention block" when it leaves its default, so
# the field a later model adds is refused with no edit here.
_MIRRORED = frozenset({
    "vocab_size", "d_model", "n_heads", "n_layers", "d_ff", "max_seq_len",
    "dtype", "norm", "ln_eps", "post_ln", "rope", "rope_theta", "mlp",
    "gelu_exact", "n_kv_heads", "attn_proj_bias", "tied_head", "use_pos_emb",
    "causal", "attn_impl", "fused_lm_ce", "remat", "dropout_rate"})


def _check_decode_args(cfg: tfm.TransformerConfig, max_len: int,
                       top_k: int) -> None:
    plain = tfm.TransformerConfig()
    unmirrored = {
        f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
        if f.name not in _MIRRORED
        and getattr(cfg, f.name) != getattr(plain, f.name)}
    if cfg.layer_types and set(cfg.layer_types) == {"attention"}:
        del unmirrored["layer_types"]      # () spelled out
    if cfg.mlp not in ("gelu", "swiglu"):
        unmirrored["mlp"] = cfg.mlp
    assert not unmirrored, (
        "_decode_layer mirrors the plain attention block (two halves, one "
        "k/v cache shape a model, a GELU or SwiGLU MLP) and nothing of: "
        + ", ".join(f"{k}={v!r}" for k, v in unmirrored.items()))
    assert cfg.causal, "decode is autoregressive — causal configs only"
    assert max_len <= cfg.max_seq_len
    assert 0 <= top_k <= cfg.vocab_size, (
        f"top_k {top_k} out of range [0, vocab_size={cfg.vocab_size}]")


def _next_token(logits, rng, sample: bool, top_k: int, temperature):
    """Greedy argmax or (top-k) temperature sampling -> (B,) int32. The
    ONE implementation shared by the scan and while_loop decode paths."""
    if not sample:
        return jnp.argmax(logits, -1).astype(jnp.int32)
    scaled = logits / temperature
    if top_k > 0:
        kth = jax.lax.top_k(scaled, top_k)[0][..., -1:]
        scaled = jnp.where(scaled >= kth, scaled, -jnp.inf)
    return jax.random.categorical(rng, scaled, -1).astype(jnp.int32)


@functools.lru_cache(maxsize=32)
def make_generate_fn(cfg: tfm.TransformerConfig, max_len: int,
                     sample: bool = False, top_k: int = 0,
                     mesh=None, chunked_prefill: bool = True):
    """Returns a jitted ``(params, prompt (B, P) int32, rng_key,
    temperature=1.0, prompt_lens=None) -> (tokens (B, max_len),
    logits (B, max_len, V))`` where tokens[:, :P] echoes the prompt and the
    rest is generated. ``prompt_lens`` (B,) int32 (clamped to [1, P])
    decodes a RAGGED batch in one call: row b teacher-forces its first
    prompt_lens[b] tokens and generates from its own boundary — under
    GREEDY decoding, token-exact vs decoding each row alone with the SAME
    prefill mechanism (sampling draws from a batch-shaped rng stream, so
    batched != solo draws).
    ``sample=False``: greedy argmax (rng/temperature unused);
    ``sample=True``: temperature sampling — temperature is a DYNAMIC
    operand, so sweeping it never recompiles; each time step consumes
    ``fold_in(key, t)``, so the draw at step t does not depend on how the
    prefix was processed. ``top_k > 0`` restricts sampling to the k most
    likely tokens.

    ``chunked_prefill`` (rectangular prompts only — ragged rows have
    per-row boundaries): positions [0, P-1) run as ONE chunked forward
    instead of P-1 sequential single-token steps. The chunk computes the
    same math but XLA may tile/accumulate it differently, so greedy
    results can differ from the tokenwise path in exact-tie cases; pass
    ``chunked_prefill=False`` when bit-parity with the ragged/tokenwise
    path matters more than prefill speed.

    ``mesh``: distributed decode — params stay in their Megatron layout
    (``tfm.param_specs``: qkv/mlp column-parallel over ``tp``), the KV
    cache is sharded batch-over-``dp`` and heads-over-``tp``, and GSPMD
    inserts the same collectives as training. Decode never gathers the
    weights."""
    _check_decode_args(cfg, max_len, top_k)

    cache_sharding = None
    if mesh is not None:
        from jax.sharding import NamedSharding
        # the cache holds the nkv UNBROADCAST heads: shard them over tp
        # only when they divide evenly (GQA/MQA can have fewer kv heads
        # than tp shards — replicate the head axis then; batch stays
        # dp-sharded either way)
        tp = mesh.shape.get("tp", 1)
        head_axis = "tp" if cfg.kv_heads % tp == 0 else None
        cache_sharding = NamedSharding(
            mesh, jax.sharding.PartitionSpec(None, "dp", head_axis,
                                             None, None))

    def gen(params, prompt, key, temperature=1.0, prompt_lens=None):
        B, P = prompt.shape
        assert P <= max_len, f"prompt length {P} > max_len {max_len}"
        # ragged batches: per-row prompt lengths — row b teacher-forces its
        # first prompt_lens[b] tokens and starts generating at its OWN
        # boundary, overwriting the rectangle's padding before any read (the
        # write for position t happens at step t-1, the read at step t), so
        # no pad token ever reaches the model or the KV cache
        plens = (jnp.full((B,), P, jnp.int32) if prompt_lens is None
                 else jnp.clip(jnp.asarray(prompt_lens, jnp.int32), 1, P))
        L, nkv, hd = cfg.n_layers, cfg.kv_heads, cfg.head_dim
        kcache = jnp.zeros((L, B, nkv, max_len, hd), cfg.dtype,
                           device=cache_sharding)
        vcache = jnp.zeros_like(kcache)
        padded = jnp.zeros((B, max_len), jnp.int32)
        padded = jax.lax.dynamic_update_slice(padded, prompt, (0, 0))

        # the per-position logits stay part of the returned contract, so
        # the prefill head runs over the whole prefix as one matmul too
        start, prefix_logits, kcache, vcache = _prefill_prefix(
            params, cfg, prompt, kcache, vcache, chunked_prefill,
            prompt_lens, want_logits=True)

        def step(carry, t):
            tok_seq, kcache, vcache = carry
            tok = jax.lax.dynamic_index_in_dim(tok_seq, t, 1, keepdims=False)
            logits, kcache, vcache = _one_token_logits(
                params, cfg, tok, kcache, vcache, t)
            # fold_in(key, t), NOT a split chain: the draw at step t is a
            # function of (key, t) alone, so skipping prefill steps (or
            # passing prompt_lens for a rectangular batch) never shifts
            # the sampling stream
            nxt = _next_token(logits, jax.random.fold_in(key, t), sample,
                              top_k, temperature)
            # teacher-force while the NEXT position is still in the row's
            # prompt, and never write past the end (the final step's sample
            # has no slot — its logits are still returned)
            idx = jnp.minimum(t + 1, max_len - 1)
            cur_next = jax.lax.dynamic_index_in_dim(tok_seq, idx, 1,
                                                    keepdims=False)
            nxt = jnp.where((t + 1) < plens, cur_next, nxt)
            nxt = jnp.where((t + 1) < max_len, nxt, cur_next)
            tok_seq = jax.lax.dynamic_update_slice(
                tok_seq, nxt[:, None], (0, idx))
            return (tok_seq, kcache, vcache), logits

        (tok_seq, _, _), logits_seq = jax.lax.scan(
            step, (padded, kcache, vcache),
            jnp.arange(start, max_len))
        logits = jnp.swapaxes(logits_seq, 0, 1)         # (B, M-start, V)
        if prefix_logits is not None:
            logits = jnp.concatenate([prefix_logits, logits], axis=1)
        return tok_seq, logits                          # (B, M, V)

    return jax.jit(gen, static_argnames=())


def generate(params, cfg: tfm.TransformerConfig, prompt, max_len: int,
             temperature: float = 0.0, rng: Optional[jax.Array] = None):
    """Convenience one-shot wrapper: ``temperature == 0`` -> greedy."""
    fn = make_generate_fn(cfg, max_len, sample=temperature > 0.0)
    if rng is None:
        rng = jax.random.PRNGKey(0)
    toks, _ = fn(params, jnp.asarray(prompt, jnp.int32), rng,
                 max(temperature, 1e-6))
    return np.asarray(toks)


@functools.lru_cache(maxsize=32)
def make_eos_generate_fn(cfg: tfm.TransformerConfig, max_len: int,
                         eos_id: int, sample: bool = False,
                         top_k: int = 0, chunked_prefill: bool = True):
    """EOS-aware decode: a ``lax.while_loop`` that EXITS EARLY once every
    row has emitted ``eos_id`` — data-dependent control flow the
    compiler-friendly way (the fixed-length scan path pays for max_len
    steps regardless; this pays only for the longest row). Finished rows
    keep emitting eos. Returns (tokens (B, max_len) — tail filled with
    eos — and t, the POSITION the loop stopped at: the number of sequence
    positions processed, counting chunk-prefilled prompt positions; loop
    ITERATIONS executed are t - (P-1) for a chunk-prefilled rectangular
    prompt). ``chunked_prefill`` as in ``make_generate_fn`` (False = the
    tokenwise path, bit-parity with ragged decodes)."""
    _check_decode_args(cfg, max_len, top_k)
    assert 0 <= eos_id < cfg.vocab_size, (
        f"eos_id {eos_id} outside vocab [0, {cfg.vocab_size}) — the model "
        "could never emit it and the loop would never exit early")

    def gen(params, prompt, key, temperature=1.0, prompt_lens=None):
        B, P = prompt.shape
        assert P <= max_len
        plens = (jnp.full((B,), P, jnp.int32) if prompt_lens is None
                 else jnp.clip(jnp.asarray(prompt_lens, jnp.int32), 1, P))
        L, nkv, hd = cfg.n_layers, cfg.kv_heads, cfg.head_dim
        kcache = jnp.zeros((L, B, nkv, max_len, hd), cfg.dtype)
        vcache = jnp.zeros_like(kcache)
        padded = jnp.full((B, max_len), eos_id, jnp.int32)
        padded = jax.lax.dynamic_update_slice(padded, prompt, (0, 0))
        # ragged batches: the rectangle's pad beyond a row's OWN length must
        # not survive an early exit — the documented contract is an
        # eos-filled tail (generation overwrites from plens[b] as it runs)
        pos = jnp.arange(max_len)[None, :]
        padded = jnp.where(pos < plens[:, None], padded, eos_id)
        finished = jnp.zeros((B,), bool)

        # rectangular prompts: chunk-prefill [0, P-1) exactly as the scan
        # path does (the while body then only ever runs decode-shaped
        # iterations — for the common serving case of a long prompt with
        # early exit this removes P-1 sequential single-token steps)
        start, _, kcache, vcache = _prefill_prefix(
            params, cfg, prompt, kcache, vcache, chunked_prefill,
            prompt_lens, want_logits=False)
        t0 = jnp.int32(start)

        def cond(state):
            t, _, _, _, finished = state
            # finished can only be set past the prompt, so this single
            # clause also keeps the teacher-forced prefix running
            return jnp.logical_and(t < max_len - 1,
                                   jnp.logical_not(jnp.all(finished)))

        def body(state):
            t, tok_seq, kcache, vcache, finished = state
            tok = jax.lax.dynamic_index_in_dim(tok_seq, t, 1, keepdims=False)
            logits, kcache, vcache = _one_token_logits(
                params, cfg, tok, kcache, vcache, t)
            # fold_in(key, t): draws depend on (key, t) alone — see
            # make_generate_fn
            nxt = _next_token(logits, jax.random.fold_in(key, t), sample,
                              top_k, temperature)
            in_prompt = (t + 1) < plens    # per-row (ragged batches)
            cur_next = jax.lax.dynamic_index_in_dim(tok_seq, t + 1, 1,
                                                    keepdims=False)
            nxt = jnp.where(in_prompt, cur_next, nxt)
            nxt = jnp.where(finished, eos_id, nxt)   # finished rows: eos
            finished = jnp.logical_or(
                finished,
                jnp.logical_and(jnp.logical_not(in_prompt), nxt == eos_id))
            tok_seq = jax.lax.dynamic_update_slice(tok_seq, nxt[:, None],
                                                   (0, t + 1))
            return (t + 1, tok_seq, kcache, vcache, finished)

        t, tok_seq, _, _, _ = jax.lax.while_loop(
            cond, body, (t0, padded, kcache, vcache, finished))
        return tok_seq, t

    return jax.jit(gen)


@functools.lru_cache(maxsize=32)
def make_beam_search_fn(cfg: tfm.TransformerConfig, max_len: int,
                        beam_size: int):
    """Returns jitted ``(params, prompt (B, P) int32) ->
    (tokens (B, K, max_len), scores (B, K))``, beams sorted best-first by
    total log-probability of the generated suffix. Same one-scan KV-cache
    machinery as sampling; beam reordering gathers the cache along the
    flattened (B*K) batch dim each step.

    Prompts are RECTANGULAR (every row length P): beam expansion starts at
    one shared boundary. For ragged batches use the greedy/sampling paths
    (``prompt_lens``) or call beam per row group of equal lengths."""
    _check_decode_args(cfg, max_len, 0)
    assert beam_size >= 1
    K = beam_size

    def beam(params, prompt):
        B, P = prompt.shape
        assert 1 <= P < max_len, "beam search must generate >= 1 token"
        L, nkv, hd = cfg.n_layers, cfg.kv_heads, cfg.head_dim
        BK = B * K
        V = cfg.vocab_size

        # -- prefill at batch B (NOT B*K: the K copies would be identical):
        # one MXU-shaped chunked forward over the whole prompt instead of
        # P sequential single-token steps; the head runs on the LAST
        # position only (full-prompt logits would be a (B, P, V) dead
        # buffer) --
        kc = jnp.zeros((L, B, nkv, max_len, hd), cfg.dtype)
        vc = jnp.zeros_like(kc)
        h, kc, vc = _chunk_hidden(params, cfg, prompt, kc, vc, 0)
        last_logits = tfm.lm_head(params, h[:, P - 1:P], cfg)[:, 0]

        # first expansion: top-min(K, V) continuations of the prompt seed
        # the beams; with K > V the surplus beams start dead (-inf) and get
        # claimed by real candidates at the next expansion (this is what
        # makes K >= V^n exhaustive)
        logp0 = jax.nn.log_softmax(last_logits.astype(jnp.float32), -1)
        k0 = min(K, V)
        scores, first_tok = jax.lax.top_k(logp0, k0)           # (B, k0)
        if k0 < K:
            scores = jnp.concatenate(
                [scores, jnp.full((B, K - k0), -1e30, jnp.float32)], axis=1)
            first_tok = jnp.concatenate(
                [first_tok, jnp.zeros((B, K - k0), first_tok.dtype)], axis=1)
        toks = jnp.zeros((B, K, max_len), jnp.int32)
        toks = jax.lax.dynamic_update_slice(
            toks, jnp.repeat(prompt[:, None, :], K, 1), (0, 0, 0))
        toks = jax.lax.dynamic_update_slice(
            toks, first_tok[:, :, None].astype(jnp.int32), (0, 0, P))
        # tile the prefilled cache to B*K once
        kcache = jnp.repeat(kc, K, axis=1)
        vcache = jnp.repeat(vc, K, axis=1)

        # -- decode: feed position t, expand into position t+1 -------------
        def step(carry, t):
            toks, scores, kcache, vcache = carry
            tok = jax.lax.dynamic_index_in_dim(
                toks.reshape(BK, max_len), t, 1, keepdims=False)
            logits, kcache, vcache = _one_token_logits(
                params, cfg, tok, kcache, vcache, t)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
            cand = scores[:, :, None] + logp.reshape(B, K, V)
            top_scores, top_idx = jax.lax.top_k(cand.reshape(B, K * V), K)
            src_beam = top_idx // V                            # (B, K)
            new_tok = (top_idx % V).astype(jnp.int32)
            # reorder beams (and their caches) by ancestry
            toks = jnp.take_along_axis(toks, src_beam[..., None], axis=1)
            gather = (jnp.arange(B)[:, None] * K + src_beam).reshape(BK)
            kcache = jnp.take(kcache, gather, axis=1)
            vcache = jnp.take(vcache, gather, axis=1)
            toks = jax.lax.dynamic_update_slice(
                toks, new_tok[:, :, None], (0, 0, t + 1))
            return (toks, top_scores, kcache, vcache), None

        (toks, scores, _, _), _ = jax.lax.scan(
            step, (toks, scores, kcache, vcache),
            jnp.arange(P, max_len - 1))
        # already best-first: every top_k (first expansion and each decode
        # step) returns descending scores
        return toks, scores

    return jax.jit(beam)


# ---------------------------------------------------------------------------
# speculative decoding (beyond reference, and beyond the plain decode above)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=16)
def make_speculative_generate_fn(cfg: tfm.TransformerConfig,
                                 draft_cfg: tfm.TransformerConfig,
                                 max_len: int, k: int = 4):
    """Greedy speculative decoding: a cheap DRAFT model proposes ``k``
    tokens per round, the TARGET verifies them in ONE chunked forward
    (``_decode_layer`` with C=k+1 — an MXU-shaped matmul instead of k+1
    bandwidth-bound single-token steps), and the longest agreeing prefix
    is accepted plus the target's own next token. The greedy case of
    arXiv:2211.17192: output is TOKEN-EXACT equal to plain greedy decoding
    with the target (pinned hard on the CPU backend; on TPU the C=k+1
    verify chunk may tile/accumulate differently from the C=1 decode
    step, so an EXACT logit tie can argmax differently — the same caveat
    as ``chunked_prefill``), only faster — each round advances between 1
    and k+1 tokens at one target forward.

    Returns jitted ``(params, draft_params, prompt (1, P) int32) ->
    (tokens (1, max_len), rounds)`` — rounds is the number of verify
    forwards after prefill, so the mean acceptance per round is
    ``(max_len - P - 1) / rounds``. Batch is fixed at 1 (speculation is a
    latency optimization; rows would accept different lengths).

    Both configs must be causal, dense, same vocab; position tables must
    cover ``max_len + k`` (the last round may write a partial chunk past
    the returned window; the tail is sliced off).
    """
    _check_decode_args(cfg, max_len, 0)
    _check_decode_args(draft_cfg, max_len, 0)
    assert cfg.vocab_size == draft_cfg.vocab_size, "vocabularies differ"
    assert k >= 1
    assert max_len + k <= cfg.max_seq_len, (
        f"need max_len + k <= target max_seq_len ({max_len}+{k} > "
        f"{cfg.max_seq_len})")
    assert max_len + k <= draft_cfg.max_seq_len

    M = max_len + k          # cache/buffer room for the last partial chunk

    def gen(params, draft_params, prompt):
        B, P = prompt.shape
        assert B == 1, "speculative decode is B=1 (latency-oriented)"
        assert 1 <= P < max_len

        def cache(c):
            L, nkv, hd = c.n_layers, c.kv_heads, c.head_dim
            return (jnp.zeros((L, B, nkv, M, hd), c.dtype),
                    jnp.zeros((L, B, nkv, M, hd), c.dtype))

        kc_t, vc_t = cache(cfg)
        kc_d, vc_d = cache(draft_cfg)
        toks = jnp.zeros((B, M + 1), jnp.int32)
        toks = jax.lax.dynamic_update_slice(toks, prompt, (0, 0))

        # -- chunked prefill: ONE forward each over the whole prompt; the
        # head runs on the LAST position only (target) or not at all
        # (draft — its prefill exists purely to build the cache) --
        t_h, kc_t, vc_t = _chunk_hidden(params, cfg, prompt, kc_t, vc_t, 0)
        t_last = tfm.lm_head(params, t_h[:, P - 1:P], cfg)[:, 0]
        first = jnp.argmax(t_last, -1).astype(jnp.int32)
        _, kc_d, vc_d = _chunk_hidden(draft_params, draft_cfg, prompt,
                                      kc_d, vc_d, 0)
        toks = jax.lax.dynamic_update_slice(toks, first[:, None], (0, P))
        n0 = jnp.int32(P + 1)
        # invariant at each round start: toks[:, :n] is the sequence, both
        # caches hold positions [0, n-1), and toks[:, n-1] has not been
        # fed to either model yet

        def cond(c):
            return c[1] < max_len

        def body(c):
            toks, n, kc_t, vc_t, kc_d, vc_d, rounds = c

            # draft proposes k tokens, one bandwidth-cheap step each.
            # k+1 steps, not k: the extra step writes the LAST proposal's
            # k/v cache entry (input d_{k-1} at position n+k-1), which the
            # next round needs whenever all k proposals are accepted (the
            # bonus token advances past it) — without it the draft attends
            # a zero entry and its acceptance rate silently degrades (the
            # output stays exact either way; the target always corrects).
            # The extra proposal itself is discarded.
            def dstep(carry, _):
                cur, pos, kc_d, vc_d = carry
                logits, kc_d, vc_d = _one_token_logits(
                    draft_params, draft_cfg, cur, kc_d, vc_d, pos)
                nxt = jnp.argmax(logits, -1).astype(jnp.int32)
                return (nxt, pos + 1, kc_d, vc_d), nxt

            last = jax.lax.dynamic_index_in_dim(toks, n - 1, 1,
                                                keepdims=False)
            (_, _, kc_d, vc_d), drafts = jax.lax.scan(
                dstep, (last, n - 1, kc_d, vc_d), None, length=k + 1)
            drafts = drafts[:k, 0]                             # (k,)

            # target verifies the whole chunk in one forward:
            # [last, d_0..d_{k-1}] at positions [n-1, n+k)
            chunk = jnp.concatenate([last[:, None], drafts[None]], 1)
            v_logits, kc_t, vc_t = _chunk_logits(params, cfg, chunk,
                                                 kc_t, vc_t, n - 1)
            targets = jnp.argmax(v_logits[0], -1).astype(jnp.int32)  # (k+1,)

            # longest agreeing prefix; emit the target's tokens (equal to
            # the draft's on the accepted prefix, its own correction after)
            agree = jnp.cumprod(
                (drafts == targets[:k]).astype(jnp.int32))
            a = jnp.sum(agree)                                 # in [0, k]
            toks = jax.lax.dynamic_update_slice(toks, targets[None], (0, n))
            return (toks, n + a + 1, kc_t, vc_t, kc_d, vc_d, rounds + 1)

        toks, n, *_, rounds = jax.lax.while_loop(
            cond, body, (toks, n0, kc_t, vc_t, kc_d, vc_d, jnp.int32(0)))
        return toks[:, :max_len], rounds

    return jax.jit(gen)
