"""chip_smoke.py — the standing proof that the main path starts on the chip.

    python chip_smoke.py        (on a machine with a TPU; no arguments)

One process drives, through the entry points a user calls, at full width:

- ``device``    a TPU backend, or exit non-zero with one line
- ``executor``  ResNet-18 / CIFAR shapes, bf16, SGD, through ``ht.Executor``
- ``flagship``  BERT-base (768/12/12/3072/30522, seq 512) through
                ``bert.make_pretrain_step``: flash attention + fused MLM CE
- ``ps``        Wide&Deep through a live local PS cluster (Hybrid, prefetch)
- ``kernels``   every Pallas kernel compiled, against its XLA fallback
- ``multichip`` (more than one chip visible) dp=4 Executor, dp2 x tp2 BERT,
                ``dryrun_multichip`` on the real devices

Each phase prints one JSON line; any failed check raises, so the exit code
is non-zero at the first failed phase and no result line is printed. The
last stdout line of a full pass is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``.

The phase functions take their sizes as arguments and ``chip=False`` drops
the checks only a TPU can meet, so tests/test_chip_smoke.py drives the
same code at tiny sizes under the CPU pin. Weights and data are random,
made from seeds; nothing is read from the network or from git.
"""
import contextlib
import dataclasses
import json
import sys
import time

import numpy as np

# Mosaic kernels appear in compiled HLO as this custom-call target; an
# interpret-mode pallas_call lowers to plain HLO and never does
_MOSAIC = "tpu_custom_call"
# the experts' grouped matmul in a compiled program: the Pallas kernel of
# `kernels/grouped_matmul.py` on every expert call since PR 59 (the compiler's
# `ragged-dot` custom call before it, which three rows here still asked for)
_GROUPED_MATMUL = "grouped_matmul"


# ---------------------------------------------------------------------------
# shared plumbing
# ---------------------------------------------------------------------------

class _CompileCounter:
    """Persistent-cache hits and misses, from jax's own monitoring events:
    a warm second run must report zero misses."""

    def __init__(self):
        from jax import monitoring
        self.hits = self.misses = 0
        monitoring.register_event_listener(self._on_event)

    def _on_event(self, name, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.hits, self.misses


_COUNTER = None


def _device_fields():
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "device_kind": d.device_kind,
            "devices": len(jax.devices())}


@contextlib.contextmanager
def _phase(name, record):
    """Time a phase and print its JSON line on success. ``record`` is the
    dict the phase body fills; a raise propagates (no line, non-zero exit)."""
    before = _COUNTER.snapshot() if _COUNTER else (0, 0)
    t0 = time.time()
    yield
    after = _COUNTER.snapshot() if _COUNTER else (0, 0)
    line = {"phase": name, **_device_fields(), **record,
            "phase_s": round(time.time() - t0, 2),
            "cache_hits": after[0] - before[0],
            "cache_misses": after[1] - before[1]}
    print(json.dumps(line), flush=True)


def _check(cond, what):
    if not cond:
        raise AssertionError(f"chip_smoke: {what}")


def _finite(x):
    return bool(np.all(np.isfinite(np.asarray(x, np.float32))))


def _timed_steps(step_once, steps):
    """Run ``steps`` steps, each closed by a host read of its loss (so no
    interval measures the enqueue). Returns the timing fields of the phase
    line — seconds to the first step (compilation included), seconds for
    the rest, and the median of the later steps — and the losses."""
    losses, dts = [], []
    for _ in range(steps):
        t0 = time.time()
        losses.append(float(step_once()))
        dts.append(time.time() - t0)
    timing = {"first_step_s": round(dts[0], 2),
              "rest_s": round(sum(dts[1:]), 3), "steps": steps,
              "late_step_ms_median": round(
                  1e3 * float(np.median(dts[len(dts) // 2:])), 2),
              "first_loss": round(losses[0], 4),
              "last_loss": round(losses[-1], 4)}
    return timing, losses


def _on_tpu_devices(arrays, n_devices=1):
    """Every array lives on exactly ``n_devices`` TPU devices."""
    for a in arrays:
        devs = a.sharding.device_set
        _check(all(d.platform == "tpu" for d in devs),
               f"array on {sorted(d.platform for d in devs)}, not tpu")
        _check(len(devs) == n_devices,
               f"array spans {len(devs)} device(s), layout says {n_devices}")


def _hbm_in_use(min_bytes):
    """``bytes_in_use`` per chip; every chip must hold at least
    ``min_bytes`` — code that never saw two devices may fill only one."""
    import jax
    used = [int(d.memory_stats()["bytes_in_use"]) for d in jax.devices()]
    _check(all(u >= min_bytes for u in used),
           f"bytes_in_use per chip {used}: some chip holds < {min_bytes}")
    return used


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def phase_device():
    """Fail unless the backend is a TPU. Returns the contract's device dict."""
    import jax
    import jaxlib
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(
            f"chip_smoke: device phase failed: jax backend is {backend!r} "
            f"({jax.devices()[0].device_kind}), not a TPU")
    from hetu_tpu.utils import use_compile_cache
    cache_dir = use_compile_cache()
    from importlib import metadata
    rec = {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
           "libtpu": metadata.version("libtpu"), "compile_cache": cache_dir}
    with _phase("device", rec):
        pass
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


# ---------------------------------------------------------------------------
# executor: ResNet-18 as examples/cnn/main.py builds and runs it
# ---------------------------------------------------------------------------

def phase_executor(*, batch=128, steps=30, n_batches=8, comm_mode=None,
                   chip=True, name="executor"):
    import hetu_tpu as ht
    from hetu_tpu.kernels import registry
    from hetu_tpu.utils import import_example_models
    models = import_example_models("cnn")

    rec = {}
    with _phase(name, rec):
        # the generator ht.data.cifar10() falls back to when no dataset is
        # on disk, at the size the phase needs (the full 50,000 take 20 s)
        train_x, labels = ht.data._synthetic_classification(
            batch * n_batches, (3, 32, 32), 10, seed=11)
        train_y = ht.data.convert_to_one_hot(labels, max_val=10)
        x = ht.dataloader_op([ht.Dataloader(train_x, batch, "train")])
        y_ = ht.dataloader_op([ht.Dataloader(train_y, batch, "train")])
        with contextlib.redirect_stdout(sys.stderr):   # "Building ..." banner
            loss, _y = models.resnet18(x, y_, 10)
        train_op = ht.optim.SGDOptimizer(learning_rate=0.1).minimize(loss)
        registry.reset_stats()
        ex = ht.Executor({"train": [loss, train_op]}, ctx=ht.tpu(0), seed=0,
                         dtype="bfloat16", comm_mode=comm_mode)
        timing, losses = _timed_steps(
            lambda: np.mean(ex.run("train")[0].asnumpy()), steps)
        params = list(ex.state["params"].values())
        import jax
        jax.block_until_ready(params)
        _check(all(np.isfinite(losses)), f"non-finite loss in {losses}")
        k = max(1, steps // 5)
        _check(np.mean(losses[-k:]) < np.mean(losses[:k]),
               f"loss not falling: {losses}")
        stats = registry.dispatch_stats()
        n_dev = 1 if ex.config.mesh is None else ex.config.mesh.size
        if chip:
            _on_tpu_devices(params, n_dev)
            if n_dev == 1:
                _check(stats.get(("fused_sgd", "pallas"), 0) > 0,
                       f"fused_sgd never took the Pallas path: {stats}")
                hlo = ex.subexecutors["train"].dump_hlo(stage="optimized")
                _check(_MOSAIC in hlo, "no Mosaic custom call in the "
                       "compiled ResNet step")
            else:
                rec["hbm_bytes_in_use"] = _hbm_in_use(1 << 20)
        rec.update({"model": "resnet18", "batch": batch, "dtype": "bfloat16",
                    "mesh": None if ex.config.mesh is None
                    else dict(ex.config.mesh.shape),
                    **timing, "dispatch": _dispatch_report()})
        ex.close()
    return rec


def _dispatch_report():
    """``dispatch_stats()`` as printable rows, every fallback with the
    reason that caused it. A backend reason on the chip is a failure: the
    only admissible reasons are shapes."""
    from hetu_tpu.kernels import registry
    rows = {f"{k}:{path}": n
            for (k, path), n in sorted(registry.dispatch_stats().items())}
    reasons = {f"{k}: {why}": n
               for (k, why), n in sorted(registry.fallback_reasons().items())}
    return {"counts": rows, "fallback_reasons": reasons}


def _check_fallback_reasons():
    from hetu_tpu.kernels import registry
    for (kernel, why), _n in registry.fallback_reasons().items():
        _check("backend" not in why,
               f"{kernel} fell back for a backend reason on the chip: {why}")


# ---------------------------------------------------------------------------
# flagship: BERT-base through bert.make_pretrain_step
# ---------------------------------------------------------------------------

def _bert_batch(rng, cfg, batch, seq, n_pred, padded):
    b = {
        "input_ids": rng.randint(0, cfg.vocab_size,
                                 (batch, seq)).astype(np.int32),
        "segment_ids": (rng.rand(batch, seq) > 0.5).astype(np.int32),
        "mlm_positions": np.sort(rng.randint(
            1, seq, (batch, n_pred)).astype(np.int32), axis=1),
        "mlm_ids": rng.randint(0, cfg.vocab_size,
                               (batch, n_pred)).astype(np.int32),
        "mlm_weights": np.ones((batch, n_pred), np.float32),
        "nsp_label": rng.randint(0, 2, (batch,)).astype(np.int32),
    }
    if padded:
        b["input_mask"] = (np.arange(seq)[None, :] < rng.randint(
            seq // 2, seq + 1, (batch, 1))).astype(np.int32)
    return b


def phase_flagship(*, cfg=None, batch=32, seq=512, n_pred=76, steps=4,
                   ref_batch=2, mesh_axes=None, chip=True, name="flagship",
                   moe=None, moe_batch=2, mla=None, mla_batch=1, dsa=None,
                   dsa_batch=1, kda=None, kda_batch=1, gdn=None, gdn_batch=1):
    """BERT pretrain steps on an unpadded then a padded batch; the loss
    must stay finite (at lr 1e-4 without warm-up AdamW's first steps
    overshoot at BERT-base size, so "falling" is not asked here). On one
    chip the loss of the kernel path (flash + fused CE) is also compared
    with the unfused dot/einsum reference on ``ref_batch`` rows, and the
    ``moe`` row runs one MoE layer of sizes ``moe`` or ``MOE_ROW``
    (``_moe_row``) and the ``mla`` row one train step of a latent-attention
    model with a shared expert, ``mla`` or ``MLA_ROW`` (``_mla_row``), the
    ``dsa`` row one of a model with learned sparse attention, ``dsa`` or
    ``DSA_ROW`` (``_dsa_row``), the ``kda`` row one of a model with Kimi Delta
    Attention beside unrotated latent attention, ``kda`` or ``KDA_ROW``
    (``_kda_row``), the ``gdn`` row one of a model with a Gated DeltaNet
    layer beside gated attention, ``gdn`` or ``GDN_ROW`` (``_gdn_row``)."""
    import jax
    from hetu_tpu.kernels.fused_ce import should_fuse
    from hetu_tpu.models import bert
    from hetu_tpu.models import transformer as tfm

    cfg = cfg or bert.BERT_BASE
    rec = {}
    with _phase(name, rec):
        mesh = None
        if mesh_axes:
            from hetu_tpu.parallel import mesh as meshlib
            mesh = meshlib.make_mesh(**mesh_axes)
        impl = tfm._resolve_attn_impl(cfg.trunk(), mesh, seq)
        masked_impl = tfm._resolve_attn_impl(
            cfg.trunk(), mesh, seq, jax.numpy.zeros((batch, 1, 1, seq)))
        fused = should_fuse(cfg.fused_mlm_ce, mesh)
        if chip:
            _check(impl == "flash" and masked_impl == "flash",
                   f"attn_impl resolved to {impl!r}/{masked_impl!r}")
            _check(fused or mesh is not None, "fused MLM CE not engaged")

        params = bert.init_params(jax.random.PRNGKey(0), cfg)
        opt = bert.init_opt_state(params)
        if mesh is not None:
            # tfm.shard_params knows the trunk's tree only; BERT's heads
            # ride bert.param_specs through the same placement recipe
            specs = bert.param_specs(cfg)
            params = jax.device_put(params, jax.tree.map(
                lambda s: jax.sharding.NamedSharding(mesh, s), specs,
                is_leaf=lambda x: isinstance(
                    x, jax.sharding.PartitionSpec)))
            opt = tfm.place_opt_state(opt, specs, mesh)
        step = bert.make_pretrain_step(cfg, mesh=mesh, lr=1e-4)
        # what the trunk's checkpoint keeps at these shapes on this device
        names, held, budget = tfm._remat_names(
            cfg.trunk(), params,
            jax.ShapeDtypeStruct((batch, seq, cfg.d_model), cfg.dtype), mesh,
            jax.ShapeDtypeStruct((batch, 1, 1, seq), np.float32))
        rec["remat"] = {"names": list(names), "held_bytes": held,
                        "budget_bytes": budget}
        rng = np.random.RandomState(0)
        timings = {}
        for label, padded in (("unpadded", False), ("padded", True)):
            b = _bert_batch(rng, cfg, batch, seq, n_pred, padded)

            def once():
                nonlocal params, opt
                loss, _parts, params, opt = step(params, opt, b)
                return loss

            timing, losses = _timed_steps(once, steps)
            _check(all(np.isfinite(losses)),
                   f"{label}: non-finite loss {losses}")
            timings[label] = {**timing,
                              "losses": [round(v, 4) for v in losses]}
            if chip and mesh is None:
                hlo = step.lower(params, opt, b).compile().as_text()
                _check(_MOSAIC in hlo,
                       f"{label}: no Mosaic custom call in the compiled step")
        jax.block_until_ready(params)
        leaves = jax.tree.leaves(params)
        if chip:
            if mesh is None:
                _on_tpu_devices(leaves, 1)
            else:
                # tp-sharded and replicated leaves alike span the mesh
                _on_tpu_devices(leaves, mesh.size)
                rec["hbm_bytes_in_use"] = _hbm_in_use(1 << 20)

        if mesh is None:
            # the repo's own reference: the same trained weights through
            # the unfused attention and the materialized-logits CE, on a
            # small padded batch — final hidden states and loss must agree
            ref_cfg = dataclasses.replace(cfg, attn_impl="dot",
                                          fused_mlm_ce=False)
            rb = _bert_batch(rng, cfg, ref_batch, seq, n_pred, True)

            def loss_and_hidden(c):
                return jax.jit(lambda p, b: (
                    bert.pretrain_loss(p, b, c, None)[0],
                    bert.encode(p, b["input_ids"], b["segment_ids"], c,
                                None, b["input_mask"])))(params, rb)

            got, h_got = loss_and_hidden(cfg)
            want, h_want = loss_and_hidden(ref_cfg)
            got, want = float(got), float(want)
            h_err = float(np.max(np.abs(
                np.asarray(h_got, np.float32)
                - np.asarray(h_want, np.float32))))
            h_scale = float(np.max(np.abs(np.asarray(h_want, np.float32))))
            _check(abs(got - want) <= 2e-2 * abs(want),
                   f"kernel-path loss {got} vs reference {want}")
            _check(np.isfinite(h_err) and h_err <= 5e-2 * h_scale,
                   f"hidden states differ by {h_err} (scale {h_scale})")
            rec["vs_reference"] = {"loss": round(got, 5),
                                   "reference_loss": round(want, 5),
                                   "hidden_max_abs_err": round(h_err, 5),
                                   "hidden_max_abs": round(h_scale, 3)}
            rec["moe"] = _moe_row(moe or MOE_ROW, moe_batch, chip)
            rec["mla"] = _mla_row(mla or MLA_ROW, mla_batch, chip)
            rec["dsa"] = _dsa_row(dsa or DSA_ROW, dsa_batch, chip)
            rec["kda"] = _kda_row(kda or KDA_ROW, kda_batch, chip)
            rec["gdn"] = _gdn_row(gdn or GDN_ROW, gdn_batch, chip)
        rec.update({"model": "bert", "d_model": cfg.d_model,
                    "n_heads": cfg.n_heads, "n_layers": cfg.n_layers,
                    "d_ff": cfg.d_ff, "vocab": cfg.vocab_size, "seq": seq,
                    "batch": batch, "attn_impl": impl,
                    "masked_attn_impl": masked_impl,
                    "mlm_ce": "fused" if fused else "einsum",
                    "mesh": None if mesh is None else dict(mesh.shape),
                    "n_params": bert.count_params(params), **timings})
    return rec


# OLMoE-1B-7B's layer (models/hf_olmoe.py), with a small vocabulary: the
# `moe` row of the flagship phase
MOE_ROW = dict(vocab_size=1024, d_model=2048, n_heads=16, n_layers=1,
               d_ff=1024, max_seq_len=1024, n_experts=64,
               n_experts_per_tok=8, norm="rmsnorm", rope=True, mlp="swiglu",
               qk_norm=True, use_pos_emb=False)


def _moe_loop_hidden(params, tokens, cfg):
    """The one-layer model's hidden states and aux losses with the MoE
    block as a loop over the experts, every expert on every token and
    masked: no sort, no gather, no grouped matmul."""
    import jax
    import jax.numpy as jnp
    from hetu_tpu.models import transformer as tfm
    layer = {k: v[0] for k, v in params["blocks"].items()}
    h = tfm.embed_tokens(params, tokens, cfg)
    h, m = tfm._block_attn(h, layer, cfg, None, None, None)
    x = m.reshape(-1, m.shape[-1])
    top_p, top_e, _, _, aux = tfm._route(x, layer, cfg)

    def one_expert(y, e):
        w1, w3, w2 = (layer[k][e].astype(x.dtype) for k in ("w1", "w3", "w2"))
        weight = jnp.sum(jnp.where(top_e == e, top_p, 0.0), -1)
        u = (jax.nn.silu(jnp.dot(x, w1, preferred_element_type=jnp.float32))
             * jnp.dot(x, w3, preferred_element_type=jnp.float32))
        out = jnp.dot(u.astype(x.dtype), w2,
                      preferred_element_type=jnp.float32)
        return y + weight[:, None] * out, None

    y, _ = jax.lax.scan(one_expert, jnp.zeros(x.shape, jnp.float32),
                        jnp.arange(cfg.n_experts))
    return h + y.astype(h.dtype).reshape(h.shape), aux


def _moe_row(sizes, batch, chip):
    """One OLMoE-width layer forward and backward through the dropless
    sort + grouped-matmul path against the loop above: hidden states, loss
    and the gradients of the router and one expert matrix agree, and no
    pick is dropped."""
    import jax
    import jax.numpy as jnp
    from hetu_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(**{"dtype": jnp.bfloat16, **sizes})
    params = tfm.init_params(jax.random.PRNGKey(1), cfg)
    tokens = jnp.asarray(np.random.RandomState(1).randint(
        0, cfg.vocab_size, (batch, cfg.max_seq_len)), jnp.int32)

    def loss_and_hidden(hidden_fn):
        def loss(p):
            h, aux = hidden_fn(p, tokens, cfg)
            return (jnp.mean(h.astype(jnp.float32) ** 2)
                    + tfm.aux_weights() @ aux), h
        return jax.jit(jax.value_and_grad(loss, has_aux=True))

    system = loss_and_hidden(tfm.forward_hidden)
    (got, h_got), g_got = system(params)
    (want, h_want), g_want = loss_and_hidden(_moe_loop_hidden)(params)
    stats = jax.jit(lambda p: tfm.moe_routing_stats(p, tokens, cfg))(params)
    dropped = int(np.sum(stats["dropped"]))
    _check(dropped == 0, f"moe: {dropped} dropped picks")
    _check(int(np.sum(stats["picks"])) == tokens.size * cfg.n_experts_per_tok,
           "moe: picks do not sum to tokens x k")
    if chip:
        hlo = system.lower(params).compile().as_text()
        _check(_GROUPED_MATMUL in hlo and _MOSAIC in hlo,
               "moe: no grouped matmul custom call in the compiled layer")
    f32 = lambda a: np.asarray(a, np.float32)
    rel = lambda a, b: float(np.sqrt(np.mean((f32(a) - f32(b)) ** 2))
                             / np.sqrt(np.mean(f32(b) ** 2)))
    errs = {"hidden": rel(h_got, h_want),
            "d_router": rel(g_got["blocks"]["router"],
                            g_want["blocks"]["router"]),
            "d_w1": rel(g_got["blocks"]["w1"], g_want["blocks"]["w1"])}
    got, want = float(got), float(want)
    _check(abs(got - want) <= 1e-2 * abs(want),
           f"moe: loss {got} vs loop {want}")
    # both sides feed bfloat16 into the MXU and accumulate in float32; the
    # grouped path rounds each pick's output to bfloat16 before the
    # weighted sum, the loop sums in float32
    _check(all(np.isfinite(v) and v <= 5e-2 for v in errs.values()),
           f"moe: relative RMS errors against the loop {errs}")
    return {"loss": round(got, 5), "loop_loss": round(want, 5),
            "rel_rms_err": {k: round(v, 5) for k, v in errs.items()},
            "dropped_picks": dropped, "tokens": int(tokens.size),
            "experts": cfg.n_experts, "per_tok": cfg.n_experts_per_tok,
            "max_over_mean": round(float(np.max(stats["max_over_mean"])), 3)}


# kanana-2-30b-a3b's two kinds of layer (models/hf_deepseek_v3.py) at the
# published widths, one layer of each, 8 of 32 experts held, a small
# vocabulary: the `mla` row of the flagship phase
MLA_ROW = dict(
    hidden_size=2048, intermediate_size=6144, moe_intermediate_size=768,
    num_attention_heads=32, num_key_value_heads=32, kv_lora_rank=512,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    first_k_dense_replace=1, num_hidden_layers=2, n_routed_experts=8,
    num_routed_experts=32, first_expert_held=0, n_shared_experts=2,
    num_experts_per_tok=6, norm_topk_prob=True, routed_scaling_factor=2.448,
    rms_norm_eps=1e-6, rope_theta=1e6, vocab_size=1024,
    max_position_embeddings=1024)


def _mla_row(sizes, batch, chip):
    """One train step of a latent-attention model with a shared expert
    (one dense layer, one expert layer, a share of the experts held) through
    `make_train_step`: on the chip the kernels run q . k and p . v at two
    head widths; the loss before the step agrees with the unfused `dot`
    path's, the step's loss is finite and no held pick is dropped."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from hetu_tpu.models import hf_deepseek_v3, transformer as tfm
    dtype = sizes.get("dtype", jnp.bfloat16)
    cfg = hf_deepseek_v3.config_from_hf(
        {k: v for k, v in sizes.items() if k != "dtype"}, dtype=dtype,
        router_bias_rate=1e-2)
    params = tfm.init_params(jax.random.PRNGKey(2), cfg)
    ids = jnp.asarray(np.random.RandomState(2).randint(
        0, cfg.vocab_size, (batch, cfg.max_seq_len + 1)), jnp.int32)
    tokens, targets = ids[:, :-1], ids[:, 1:]
    impl = tfm._resolve_attn_impl(cfg, None, cfg.max_seq_len)
    if chip:
        _check(impl == "flash", f"mla: attn_impl resolved to {impl!r}")
    loss_of = lambda c: float(jax.jit(
        lambda p: tfm.loss_fn(p, tokens, targets, c))(params))
    got = loss_of(cfg)
    want = loss_of(dataclasses.replace(cfg, attn_impl="dot",
                                       fused_lm_ce=False))
    _check(abs(got - want) <= 1e-2 * abs(want),
           f"mla: kernel-path loss {got} vs dot path {want}")
    stats = jax.jit(lambda p: tfm.moe_routing_stats(p, tokens, cfg))(params)
    dropped = int(np.sum(stats["dropped"]))
    _check(dropped == 0, f"mla: {dropped} dropped picks")
    opt = tfm.init_opt_state(params)
    # compiled once: the text is read from the program that then runs
    step = tfm.make_train_step(cfg, lr=3e-6).lower(
        params, opt, tokens, targets).compile()
    if chip:
        hlo = step.as_text()
        _check(all(k in hlo for k in ("flash_fwd", "flash_bwd_dqkv",
                                      _GROUPED_MATMUL)),
               "mla: a kernel is missing from the compiled step")
    loss, params, opt = step(params, opt, tokens, targets)
    _check(_finite(loss), f"mla: step loss {float(loss)}")
    picks = np.asarray(opt["m"]["blocks"][1][tfm.ROUTER_BIAS])
    _check(picks.sum() == tokens.size * cfg.n_experts_per_tok,
           "mla: the step's counter does not hold tokens x k picks")
    return {"loss": round(got, 5), "dot_loss": round(want, 5),
            "step_loss": round(float(loss), 5), "attn_impl": impl,
            "heads": cfg.n_heads, "qk_dim": cfg.mla.qk_dim,
            "v_dim": cfg.mla.v_dim, "d_ff_shared": cfg.d_ff_shared,
            "held_picks": int(np.sum(stats["held"])),
            "dropped_picks": dropped, "tokens": int(tokens.size)}


# Kimi-Linear-48B-A3B's two kinds of mixer (models/hf_kimi_linear.py) at the
# published widths: a KDA layer with the dense MLP (a quarter of its width)
# and a latent-attention layer without rotation over 8 of 32 experts held, a
# small vocabulary, 1,024 tokens (16 chunks of 64): the `kda` row of the
# flagship phase
KDA_ROW = dict(
    hidden_size=2304, intermediate_size=2304, moe_intermediate_size=1024,
    num_attention_heads=32, num_key_value_heads=32, kv_lora_rank=512,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    mla_use_nope=True, first_k_dense_replace=1, num_hidden_layers=2,
    linear_attn_config=dict(kda_layers=[1], full_attn_layers=[2],
                            num_heads=32, head_dim=128,
                            short_conv_kernel_size=4),
    num_experts=8, num_routed_experts=32, first_expert_held=0,
    num_shared_experts=1, num_experts_per_token=8, moe_renormalize=True,
    routed_scaling_factor=2.446, rms_norm_eps=1e-5, vocab_size=1024,
    model_max_length=1024)


def _delta_rule_err_f64(t):
    """The scan's output ``t["o"]`` against the gated delta rule over
    POSITIONS in numpy float64 on the scan's own inputs (``kda_terms`` /
    ``gdn_terms``: the first sequence's q, k, v, beta and g, a channel's (T,
    H, K) or a head's (T, H)) -> the relative RMS error."""
    f64 = lambda x: np.asarray(x).astype(np.float64)[0]
    q, k, v, g, beta = (f64(t[n]) for n in ("q", "k", "v", "g", "beta"))
    S, want = np.zeros(q.shape[1:] + v.shape[-1:]), np.empty_like(v)
    for i in range(q.shape[0]):
        S *= np.exp(g[i]).reshape(g[i].shape + (1,) * (S.ndim - g[i].ndim))
        u = beta[i][:, None] * (v[i] - np.einsum("hkv,hk->hv", S, k[i]))
        S += k[i][..., None] * u[:, None, :]
        want[i] = np.einsum("hkv,hk->hv", S, q[i])
    return float(np.sqrt(np.mean((f64(t["o"]) - want) ** 2)
                         / np.mean(want ** 2)))


def _kda_row(sizes, batch, chip):
    """One train step of a model with a Kimi Delta Attention layer and a
    latent-attention layer that rotates nothing through `make_train_step`:
    the chunked rule's output on the first layer's own inputs (every head; on
    the chip the Mosaic kernel's, and the row says which form served) agrees
    with the recurrence over positions in float64, every value finite
    where the cumulated log-decay is past float32's 1 / exp(G); the step's
    loss is finite and no held pick is dropped."""
    import jax
    import jax.numpy as jnp
    from hetu_tpu.models import hf_kimi_linear, transformer as tfm
    dtype = sizes.get("dtype", jnp.bfloat16)
    cfg = hf_kimi_linear.config_from_hf(
        {k: v for k, v in sizes.items() if k not in ("dtype", "kda_chunk")},
        dtype=dtype, router_bias_rate=1e-2,
        **({"kda_chunk": sizes["kda_chunk"]} if "kda_chunk" in sizes else {}))
    params = tfm.init_params(jax.random.PRNGKey(3), cfg)
    ids = jnp.asarray(np.random.RandomState(3).randint(
        0, cfg.vocab_size, (batch, cfg.max_seq_len + 1)), jnp.int32)
    tokens, targets = ids[:, :-1], ids[:, 1:]
    from hetu_tpu.telemetry import tracing
    noted = len(tracing.forms("kda.scan"))
    t = jax.device_get(jax.jit(lambda p: tfm.kda_terms(
        p, tokens, cfg))(params))
    err = _delta_rule_err_f64(t)
    _check(np.isfinite(err) and err <= 1e-4,
           f"kda: the chunked rule is {err} from the recurrence")
    stats = jax.jit(lambda p: tfm.moe_routing_stats(p, tokens, cfg))(params)
    dropped = int(np.sum(stats["dropped"]))
    _check(dropped == 0, f"kda: {dropped} dropped picks")
    opt = tfm.init_opt_state(params)
    step = tfm.make_train_step(cfg, lr=3e-6).lower(
        params, opt, tokens, targets).compile()
    served = sorted({r["form"] + (f" ({r['reason']})" if r["reason"] else "")
                     for r in tracing.forms("kda.scan")[noted:]})
    if chip:
        hlo = step.as_text()
        _check(all(k in hlo for k in ("flash_fwd", "hetu_kda_scan",
                                      "kda_fwd", "kda_bwd")),
               "kda: a kernel or a scope is missing from the compiled step")
        _check(served == ["kernel"], f"kda: the scan was served by {served}")
    loss, params, opt = step(params, opt, tokens, targets)
    _check(_finite(loss), f"kda: step loss {float(loss)}")
    return {"step_loss": round(float(loss), 5),
            "scan_rel_rms_err_vs_f64": float(f"{err:.3g}"),
            "chunk_log_decay_min": round(
                float(t["chunk_log_decay_min"]), 2),
            "heads": cfg.kda.n_heads, "head_dim": cfg.kda.head_dim,
            "chunk": cfg.kda.chunk, "rotate": cfg.mla.rotate,
            "scan_served_by": served,
            "held_picks": int(np.sum(stats["held"])),
            "dropped_picks": dropped, "tokens": int(tokens.size)}


# Qwen3-Next-80B-A3B's two kinds of mixer (models/hf_qwen3_next.py) at the
# published widths: a Gated DeltaNet layer and a gated-attention layer (16
# heads of 256 on 2, a quarter of a head rotated) over 8 of 32 experts held
# and the gated shared expert, a small vocabulary, 1,024 tokens (16 chunks of
# 64): the `gdn` row of the flagship phase
GDN_ROW = dict(
    hidden_size=2048, intermediate_size=5120, moe_intermediate_size=512,
    shared_expert_intermediate_size=512, num_attention_heads=16,
    num_key_value_heads=2, head_dim=256, partial_rotary_factor=0.25,
    rope_theta=1e7, full_attention_interval=2, num_hidden_layers=2,
    linear_num_key_heads=16, linear_num_value_heads=32,
    linear_key_head_dim=128, linear_value_head_dim=128,
    linear_conv_kernel_dim=4, num_experts=8, num_routed_experts=32,
    first_expert_held=0, num_experts_per_tok=10, norm_topk_prob=True,
    rms_norm_eps=1e-6, vocab_size=1024, max_position_embeddings=1024)


def _gdn_row(sizes, batch, chip):
    """One train step of a model with a Gated DeltaNet layer and a
    gated-attention layer through `make_train_step`: the chunked rule's
    output on the first layer's own inputs, the decay a head's, agrees with
    the recurrence over positions in float64 in BOTH forms that can reach a
    chip: the one the step runs (pass "step": on the chip the Mosaic head
    kernels, `gdn_fwd` / `gdn_bwd`) and the XLA form that serves a mesh, a
    ragged T or another chunk, g broadcast over the head's columns (pass
    "xla": the kernels' rule told it is off the chip), every value finite
    where the cumulated log-decay is past float32's 1 / exp(G); the step's
    loss is finite and no held pick is dropped."""
    import jax
    import jax.numpy as jnp
    from hetu_tpu.kernels import kda as kda_kernel
    from hetu_tpu.models import hf_qwen3_next, transformer as tfm
    from hetu_tpu.telemetry import tracing
    dtype = sizes.get("dtype", jnp.bfloat16)
    cfg = hf_qwen3_next.config_from_hf(
        {k: v for k, v in sizes.items() if k not in ("dtype", "gdn_chunk")},
        dtype=dtype,
        **({"gdn_chunk": sizes["gdn_chunk"]} if "gdn_chunk" in sizes else {}))
    params = tfm.init_params(jax.random.PRNGKey(3), cfg)
    ids = jnp.asarray(np.random.RandomState(3).randint(
        0, cfg.vocab_size, (batch, cfg.max_seq_len + 1)), jnp.int32)
    tokens, targets = ids[:, :-1], ids[:, 1:]
    errs, served, on_tpu = {}, {}, kda_kernel._on_tpu
    for form in ("step", "xla"):
        if form == "xla":
            kda_kernel._on_tpu = lambda: False
        noted = len(tracing.forms("kda.scan"))
        try:
            t = jax.device_get(jax.jit(lambda p: tfm.gdn_terms(
                p, tokens, cfg))(params))
        finally:
            kda_kernel._on_tpu = on_tpu
        served[form] = sorted({r["form"] for r in
                               tracing.forms("kda.scan")[noted:]})
        errs[form] = _delta_rule_err_f64(t)
        _check(np.isfinite(errs[form]) and errs[form] <= 1e-4,
               f"gdn: the chunked rule ({form}: {served[form]}) is "
               f"{errs[form]} from the recurrence")
    stats = jax.jit(lambda p: tfm.moe_routing_stats(p, tokens, cfg))(params)
    dropped = int(np.sum(stats["dropped"]))
    _check(dropped == 0, f"gdn: {dropped} dropped picks")
    opt = tfm.init_opt_state(params)
    step = tfm.make_train_step(cfg, lr=3e-6).lower(
        params, opt, tokens, targets).compile()
    if chip:
        hlo = step.as_text()
        _check(all(k in hlo for k in ("flash_fwd", "hetu_gdn_scan",
                                      "hetu_attn_gate", "gdn_fwd", "gdn_bwd")),
               "gdn: a kernel or a scope is missing from the compiled step")
        _check(served == {"step": ["head-kernel"], "xla": ["xla"]},
               f"gdn: the scan was served by {served}")
    loss, params, opt = step(params, opt, tokens, targets)
    _check(_finite(loss), f"gdn: step loss {float(loss)}")
    return {"step_loss": round(float(loss), 5),
            "scan_rel_rms_err_vs_f64": {
                form: float(f"{err:.3g}") for form, err in errs.items()},
            "chunk_log_decay_min": round(
                float(t["chunk_log_decay_min"]), 2),
            "key_heads": cfg.gdn.n_k_heads, "value_heads": cfg.gdn.n_v_heads,
            "head_dim": cfg.gdn.k_dim, "chunk": cfg.gdn.chunk,
            "scan_served_by": served,
            "attn_heads": [cfg.n_heads, cfg.kv_heads, cfg.head_dim,
                           cfg.rope_dim],
            "held_picks": int(np.sum(stats["held"])),
            "dropped_picks": dropped, "tokens": int(tokens.size)}


# Keye-VL-2.0-30B-A3B's layer (models/hf_keye.py) at the published widths, 8
# of 32 experts held, a small vocabulary, 2,048 tokens of which a query keeps
# 512: the `dsa` row of the flagship phase
DSA_ROW = dict(
    hidden_size=2048, intermediate_size=6144, moe_intermediate_size=768,
    num_attention_heads=32, num_key_value_heads=4, head_dim=128,
    num_hidden_layers=1, num_experts=8, num_routed_experts=32,
    first_expert_held=0, num_experts_per_tok=8, norm_topk_prob=True,
    rms_norm_eps=1e-6, rope_theta=1e7, vocab_size=1024,
    max_position_embeddings=2048,
    sa_config=dict(indexer_num_heads=16, indexer_head_dim=64,
                   indexer_num_kv_heads=1, topk=512))


def _dsa_row(sizes, batch, chip):
    """One train step of a model with learned sparse attention (an indexer
    picks `topk` keys a query; a share of the experts held) through
    `make_train_step`: on the chip the flash kernels attend under the packed
    row masks; the loss before the step agrees with the unfused `dot` path's
    under the boolean mask, the counter reads the closed form of the kept
    pairs, the indexer's loss is positive and the step's loss finite."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from hetu_tpu.models import hf_keye, transformer as tfm
    dtype = sizes.get("dtype", jnp.bfloat16)
    cfg = hf_keye.config_from_hf(
        {k: v for k, v in sizes.items() if k != "dtype"}, dtype=dtype)
    params = tfm.init_params(jax.random.PRNGKey(3), cfg)
    T, k = cfg.max_seq_len, cfg.dsa.top_k
    ids = jnp.asarray(np.random.RandomState(3).randint(
        0, cfg.vocab_size, (batch, T + 1)), jnp.int32)
    tokens, targets = ids[:, :-1], ids[:, 1:]
    impl = tfm._resolve_attn_impl(cfg, None, T)
    if chip:
        _check(impl == "flash", f"dsa: attn_impl resolved to {impl!r}")
    loss_of = lambda c: float(jax.jit(
        lambda p: tfm.loss_fn(p, tokens, targets, c))(params))
    got = loss_of(cfg)
    want = loss_of(dataclasses.replace(cfg, attn_impl="dot",
                                       fused_lm_ce=False))
    _check(abs(got - want) <= 1e-2 * abs(want),
           f"dsa: kernel-path loss {got} vs dot path {want}")
    stats = jax.jit(lambda p: tfm.dsa_stats(p, tokens, cfg))(params)
    kept = int(np.sum(stats["kept"]))
    closed = batch * cfg.n_layers * (
        min(T, k) * (min(T, k) + 1) // 2 + max(T - k, 0) * k)
    _check(kept == closed, f"dsa: {kept} kept pairs, closed form {closed}")
    _check(float(np.min(stats["loss"])) > 0,
           f"dsa: indexer loss {np.asarray(stats['loss']).tolist()}")
    opt = tfm.init_opt_state(params)
    step = tfm.make_train_step(cfg, lr=3e-6).lower(
        params, opt, tokens, targets).compile()
    if chip:
        hlo = step.as_text()
        _check(all(k in hlo for k in ("flash_fwd", "flash_bwd_dqkv",
                                      _GROUPED_MATMUL)),
               "dsa: a kernel is missing from the compiled step")
    loss, params, opt = step(params, opt, tokens, targets)
    _check(_finite(loss), f"dsa: step loss {float(loss)}")
    return {"loss": round(got, 5), "dot_loss": round(want, 5),
            "step_loss": round(float(loss), 5), "attn_impl": impl,
            "heads": cfg.n_heads, "kv_heads": cfg.kv_heads,
            "head_dim": cfg.head_dim, "top_k": k, "kept_pairs": kept,
            "kept_pair_pct": round(
                100.0 * kept / (batch * cfg.n_layers * T * (T + 1) // 2), 2),
            "index_loss": round(float(np.sum(stats["loss"])), 5),
            "tokens": int(tokens.size)}


# ---------------------------------------------------------------------------
# ps: Wide&Deep through a live local PS cluster
# ---------------------------------------------------------------------------

def phase_ps(*, batch=128, steps=20, feature_dim=100000, embedding_size=16,
             n_servers=2, chip=True):
    from hetu_tpu import ps as ps_pkg
    from hetu_tpu.chaos import check_update_accounting
    from hetu_tpu.kernels import registry
    from hetu_tpu.ps.local_cluster import local_cluster
    from hetu_tpu.utils import import_example_models

    rec = {}
    with _phase("ps", rec):
        registry.reset_stats()
        with local_cluster(n_servers=n_servers, n_workers=1):
            import hetu_tpu as ht
            models = import_example_models("ctr")
            from models.load_data import load_criteo_data
            (dense_x, sparse_x, y), _ = load_criteo_data(
                feature_dimension=feature_dim, n_train=batch * 8, n_test=64)
            dense = ht.dataloader_op([ht.Dataloader(dense_x, batch, "train")])
            sparse = ht.dataloader_op(
                [ht.Dataloader(sparse_x, batch, "train")])
            y_ = ht.dataloader_op([ht.Dataloader(y, batch, "train")])
            loss, _y, _labels, train_op = models.wdl_criteo(
                dense, sparse, y_, feature_dimension=feature_dim,
                embedding_size=embedding_size)
            ex = ht.Executor({"train": [loss, train_op]}, ctx=ht.tpu(0),
                             comm_mode="Hybrid", seed=0, prefetch=True)
            try:
                timing, losses = _timed_steps(
                    lambda: np.mean(ex.run("train")[0].asnumpy()), steps)
                ex.ps_runtime.drain()
                comm = ps_pkg.get_worker_communicate()
                acct = check_update_accounting(
                    comm.ClientStats(),
                    [comm.ServerStats(s) for s in range(n_servers)])
                _check(acct["client_pushes_ok"] > 0, "no PS push completed")
                _check(all(np.isfinite(losses)),
                       f"non-finite loss in {losses}")
                mesh = ex.config.mesh   # Hybrid: dp over every chip visible
                if chip:
                    _on_tpu_devices(ex.state["params"].values(),
                                    1 if mesh is None else mesh.size)
                perf = dict(ex.ps_runtime.perf)
            finally:
                ex.close()
                ps_pkg.worker_finish()
        rec.update({"model": "wdl_criteo", "batch": batch,
                    "table": [feature_dim, embedding_size],
                    "servers": n_servers, "comm_mode": "Hybrid",
                    "mesh": None if mesh is None else dict(mesh.shape),
                    **timing,
                    "pushes_ok": acct["client_pushes_ok"],
                    "server_updates": acct["server_updates"],
                    "ps_perf": perf, "dispatch": _dispatch_report()})
    return rec


# ---------------------------------------------------------------------------
# kernels: each Pallas kernel compiled, against its XLA fallback
# ---------------------------------------------------------------------------

# one eligible shape per kernel, taken from the phases above or from the
# bench cell that uses the kernel
KERNEL_SHAPES = {
    "flash": (2, 12, 512, 64),           # BERT-base heads at seq 512, bf16
    # (batch, heads, seq, q/k width, v/o width) of kanana-2-30b-a3b's and
    # lfm2-8b-a1b's attention calls, causal bf16 on three arrays
    "flash_bwd_dqkv": {"kanana": (4, 32, 8192, 192, 128),
                       "lfm2": (4, 32, 8192, 64, 64)},
    # (batch, heads, seq, head width, window): laguna-xs.2's window layers
    # at a small size, four 512 x 512 tiles a head, causal bf16; and
    # smallthinker-21b-a3b's at half its sequence: ONE group of seven query
    # heads, a window of 4,096 = eight 512-key tiles deep
    "flash_window": {"laguna": (1, 8, 2048, 128, 512),
                     "smallthinker": (1, 7, 8192, 128, 4096)},
    "fused_ce": (256, 768, 30522),       # MLM rows x d_model x vocab, bf16
    "embed_grad": (3328, 128, 100000),   # 128 x 26 slots, published width
    "csr_spmm": (4096, 1024, 1024, 128),  # nnz, nrow, K, F
    "quant": (512 * 512, 256),           # the comm_quant_dp MLP grad, block
    "opt": (512, 512, 3, 3),             # ResNet-18's largest conv weight
    "rope": (2, 1024, 4, 128, 64),       # kanana's q: heads of 128 + 64, bf16
    # (batch, seq, heads, k/v heads, head width, rotary width (0 = all),
    # YaRN) of the rotate-half cells' q and k, bf16, read out of the fused
    # [q | k | v] projection: Ouro's one sequence, a laguna-xs.2 full layer
    # (half a head turns, YaRN's table) and a window layer (64 heads on 8),
    # lfm2's heads of 64
    "rope_halves": {"ouro": (1, 4096, 16, 16, 128, 0, False),
                    "laguna-full": (1, 16384, 48, 8, 128, 64, True),
                    "laguna-window": (1, 16384, 64, 8, 128, 0, False),
                    # smallthinker-21b-a3b's window layers: 28 on 4, groups
                    # of seven, q 28 lane tiles wide
                    "smallthinker-window": (1, 16384, 28, 4, 128, 0, False),
                    "lfm2": (4, 8192, 32, 8, 64, 0, False)},
    # (batch, seq, heads, head width, groups, state, chunk): a quarter of
    # granite-4.0-h-micro's scan, eight chunks of 256, bf16
    "ssd": (1, 2048, 64, 64, 1, 128, 256),
    # a quarter of nemotron-twotower-30b-a3b's scan: B and C in 8 groups of
    # 8 heads (8 heads a grid step where Granite takes 16), sixteen chunks
    # of ONE lane tile
    "ssd_groups": (1, 2048, 64, 64, 8, 128, 128),
    # (rows, K, N, rows a group): a quarter of the rows of
    # nemotron-twotower-30b-a3b's `w1` call at its widths, an odd number of
    # lane tiles and a half tile, bf16; 768 rows in eight uneven groups, one
    # empty, edges inside the row tiles, the other rows in no group
    "grouped_matmul": (12288, 2688, 1856, (90, 0, 130, 200, 64, 100, 120, 64)),
}


def phase_kernels(*, shapes=None, chip=True):
    import jax
    import jax.numpy as jnp
    from hetu_tpu import comm_quant
    from hetu_tpu.kernels import (csr_spmm, embed_grad, fused_opt,
                                  quant_comm, registry)
    from hetu_tpu.kernels import flash_attention as fa
    from hetu_tpu.kernels.flash_attention import (flash_attention_btd,
                                                  mha_reference)
    from hetu_tpu.kernels.fused_ce import (fused_linear_nll,
                                           linear_nll_reference)
    from hetu_tpu.kernels.rope import rope_halves, rope_interleaved
    from hetu_tpu.models.transformer import (YarnConfig, _rope,
                                             _grouped_matmul,
                                             _rope_interleaved, _ssd,
                                             _ssd_kernels)

    shapes = {**KERNEL_SHAPES, **(shapes or {})}
    rng = np.random.RandomState(0)
    results = {}

    def compare(name, kernel_fn, fallback_fn, args, *, atol, rtol=0.0,
                exact=False):
        """Both sides under jit (eager XLA differs from compiled XLA by
        1-ulp rewrites); the kernel side must hold a Mosaic custom call."""
        def forced(*a):
            with registry.active("force"):
                return kernel_fn(*a)

        def off(*a):
            with registry.active("off"):
                return fallback_fn(*a)

        kj = jax.jit(forced)
        if chip:
            _check(_MOSAIC in kj.lower(*args).compile().as_text(),
                   f"{name}: kernel side has no Mosaic custom call "
                   "(interpret mode?)")
        got = jax.tree.leaves(jax.tree.map(np.asarray, kj(*args)))
        want = jax.tree.leaves(jax.tree.map(np.asarray,
                                            jax.jit(off)(*args)))
        _check(len(got) == len(want), f"{name}: output structure differs")
        err = 0.0
        for g, w in zip(got, want):
            g32, w32 = g.astype(np.float32), w.astype(np.float32)
            _check(g.shape == w.shape and _finite(g32),
                   f"{name}: bad shape or non-finite output")
            err = max(err, float(np.max(np.abs(g32 - w32))) if g.size else 0)
            if exact:
                _check(np.array_equal(g, w), f"{name}: not bit-identical "
                       f"(max abs err {err})")
            else:
                np.testing.assert_allclose(g32, w32, atol=atol, rtol=rtol,
                                           err_msg=f"chip_smoke: {name}")
        results[name] = {"max_abs_err": err}

    rec = {"kernels": results}
    with _phase("kernels", rec):
        # -- flash attention, fwd + bwd, bf16 (tests/test_attention.py's
        # bf16 tolerance), through the entry the trunk calls: the fused
        # projection's (batch, seq, [q|k|v] x heads x head_dim) array in,
        # (batch, seq, heads x head_dim) out -------------------------------
        b, h, s, d = shapes["flash"]
        qkv = jnp.asarray(rng.randn(b, s, 3 * h * d), jnp.bfloat16)
        k_bias = jnp.asarray(np.where(
            np.arange(s)[None, :] < rng.randint(s // 2, s + 1, (b, 1)),
            0.0, -1e9), jnp.float32)

        def reference_btd(qkv, causal, k_bias):
            q, k, v = (x.reshape(b, s, h, d).transpose(0, 2, 1, 3)
                       for x in jnp.split(qkv, 3, axis=-1))
            out = mha_reference(q, k, v, causal=causal, k_bias=k_bias)
            return out.transpose(0, 2, 1, 3).reshape(b, s, h * d)

        def attn_and_grads(fn, causal, bias):
            def run(qkv):
                def loss(qkv):
                    return jnp.sum(fn(qkv, causal, bias)
                                   .astype(jnp.float32) ** 2)
                return fn(qkv, causal, bias), jax.grad(loss)(qkv)
            return run

        def flash(qkv, causal, k_bias):
            return flash_attention_btd(qkv, h, causal, k_bias=k_bias)

        for label, causal, bias in (("flash_causal", True, None),
                                    ("flash_key_padding", False, k_bias)):
            compare(label, attn_and_grads(flash, causal, bias),
                    attn_and_grads(reference_btd, causal, bias), (qkv,),
                    atol=2e-2 * max(1.0, float(s) ** 0.5), rtol=2e-2)

        # -- the one backward kernel of a many-tile sequence against the
        # XLA blockwise backward, on the forward kernel's o and lse, at two
        # cells' calls (tests/test_attention.py's bf16 tolerance) ----------
        for cell, (b, h, s, d, dv) in shapes["flash_bwd_dqkv"].items():
            qkv = tuple(jnp.asarray(rng.randn(b, s, h * w), jnp.bfloat16)
                        for w in (d, d, dv))
            do = jnp.asarray(rng.randn(b, s, h * dv), jnp.bfloat16)
            kw = dict(n_heads=h, scale=d ** -0.5, causal=True)
            o, lse = jax.jit(lambda qkv: fa._fwd_pallas(
                qkv, h, None, kw["scale"], True, None, None,
                interpret=not chip))(qkv)

            def backward(fn, **how):
                return jax.jit(lambda qkv, o, lse, do: fn(
                    (qkv, o, lse, None), do, **kw, **how))

            kernel = backward(fa._bwd_pallas, block_q=None, block_k=None,
                              interpret=not chip)
            if chip:
                hlo = kernel.lower(qkv, o, lse, do).compile().as_text()
                _check(fa.FLASH_BWD_DQKV in hlo and _MOSAIC in hlo,
                       f"{cell}: no {fa.FLASH_BWD_DQKV} in the compiled call")
            got = kernel(qkv, o, lse, do)
            want = backward(fa._bwd_blockwise, block_k=128)(qkv, o, lse, do)
            err = 0.0
            for g, w in zip(got, want):
                g, w = (np.asarray(x, np.float32) for x in (g, w))
                _check(g.shape == w.shape and _finite(g),
                       f"{cell}: bad shape or non-finite gradient")
                np.testing.assert_allclose(
                    g, w, atol=2e-2, rtol=2e-2,
                    err_msg=f"chip_smoke: {fa.FLASH_BWD_DQKV} at {cell}")
                err = max(err, float(np.max(np.abs(g - w))))
            results[f"{fa.FLASH_BWD_DQKV}:{cell}"] = {"max_abs_err": err}

        # -- a sliding window: the forward against the unfused reference
        # under the window's mask, the one backward kernel against the XLA
        # blockwise backward under it, on the forward kernel's o and lse ----
        for cell, (b, h, s, d, window) in shapes["flash_window"].items():
            qkv = tuple(jnp.asarray(rng.randn(b, s, h * d), jnp.bfloat16)
                        for _ in range(3))
            do = jnp.asarray(rng.randn(b, s, h * d), jnp.bfloat16)
            kw = dict(n_heads=h, scale=d ** -0.5, causal=True, window=window)
            o, lse = jax.jit(lambda qkv: fa._fwd_pallas(
                qkv, h, None, kw["scale"], True, None, None,
                interpret=not chip, window=window))(qkv)
            heads = lambda x: x.reshape(b, s, h, d).transpose(0, 2, 1, 3)
            want_o = mha_reference(*map(heads, qkv), causal=True,
                                   window=window)
            np.testing.assert_allclose(
                np.asarray(heads(o), np.float32),
                np.asarray(want_o, np.float32), atol=2e-2, rtol=2e-2,
                err_msg="chip_smoke: flash_fwd, window")
            got = jax.jit(lambda qkv, o, lse, do: fa._bwd_pallas(
                (qkv, o, lse, None), do, **kw, block_q=None, block_k=None,
                interpret=not chip))(qkv, o, lse, do)
            want = jax.jit(lambda qkv, o, lse, do: fa._bwd_blockwise(
                (qkv, o, lse, None), do, **kw, block_k=128))(qkv, o, lse, do)
            err = float(np.max(np.abs(np.asarray(heads(o), np.float32)
                                      - np.asarray(want_o, np.float32))))
            for g, w in zip(got, want):
                g, w = (np.asarray(x, np.float32) for x in (g, w))
                _check(g.shape == w.shape and _finite(g),
                       "window: bad shape or non-finite gradient")
                np.testing.assert_allclose(
                    g, w, atol=2e-2, rtol=2e-2,
                    err_msg="chip_smoke: the backward kernel under a window")
                err = max(err, float(np.max(np.abs(g - w))))
            results[f"flash_window:{cell}"] = {"max_abs_err": err}

        # -- fused linear + softmax CE, fwd + bwd, bf16 -------------------
        n, dm, vocab = shapes["fused_ce"]
        hh = jnp.asarray(rng.randn(n, dm) * 0.5, jnp.bfloat16)
        ww = jnp.asarray(rng.randn(vocab, dm) * 0.05, jnp.bfloat16)
        bb = jnp.asarray(rng.randn(vocab) * 0.1, jnp.float32)
        tt = jnp.asarray(rng.randint(0, vocab, (n,)), jnp.int32)

        def ce_and_grads(fn):
            def run(h, w, b):
                return (fn(h, w, b, tt),
                        jax.grad(lambda h, w, b: jnp.mean(fn(h, w, b, tt)),
                                 argnums=(0, 1, 2))(h, w, b))
            return run

        compare("fused_ce", ce_and_grads(fused_linear_nll),
                ce_and_grads(linear_nll_reference), (hh, ww, bb),
                atol=2e-2, rtol=2e-2)

        # -- q's rotary columns in one pass, forward and transposed, bf16:
        # the float32 products and sum of `_rope_interleaved` in its order,
        # so on the chip to the bit (this host's compiler contracts the sum
        # differently in a few entries of 100,000: one bf16 unit there)
        b, s, h, nope, rot = shapes["rope"]
        rx, rg = (jnp.asarray(rng.randn(b, s, h * (nope + rot)),
                              jnp.bfloat16) for _ in range(2))

        def rotated_and_cotangent(fn):
            def run(x, g):
                out, vjp = jax.vjp(
                    lambda x: fn(x, 0, 1e6, nope + rot, nope), x)
                return out, vjp(g)[0]
            return run

        compare("rope_pairs", rotated_and_cotangent(rope_interleaved),
                rotated_and_cotangent(_rope_interleaved), (rx, rg),
                atol=2 ** -5, exact=chip)

        # -- the same kernel on rotate-half columns: q and k where they
        # stand in the projection against `_rope` of their slices, forward
        # and transposed (the cotangent laid into the projection's width)
        for cell, (b, s, h, kv, d, rot, yarn) in shapes["rope_halves"].items():
            yarn = YarnConfig(64.0, 4096, 64.0, 1.0, 1.4158883) if yarn else None
            cut = ((0, h * d), (h * d, kv * d))
            rx = jnp.asarray(rng.randn(b, s, (h + 2 * kv) * d), jnp.bfloat16)
            rg = tuple(jnp.asarray(rng.randn(b, s, w), jnp.bfloat16)
                       for _, w in cut)

            def q_k_and_cotangent(fn):
                def run(x, g):
                    out, vjp = jax.vjp(lambda x: tuple(
                        fn(x, at, 0, 5e5, d, rot, yarn) for at in cut), x)
                    return out, vjp(g)[0]
                return run

            compare(f"rope_halves:{cell}",
                    q_k_and_cotangent(lambda x, at, *rope: rope_halves(
                        x, *rope, at=at)),
                    q_k_and_cotangent(lambda x, at, *rope: _rope(
                        x[..., at[0]:at[0] + at[1]], *rope)), (rx, rg),
                    atol=2 ** -5, exact=chip)

        # -- the chunked Mamba-2 scan: y and the five cotangents against
        # `_ssd`'s einsums, each over its own largest entry (d dt is in the
        # hundreds where dx is in units)
        for name in ("ssd", "ssd_groups"):
            b, s, h, d, groups, state, chunk = shapes[name]
            sx, sg = (jnp.asarray(rng.randn(b, s, h, d), t)
                      for t in (jnp.bfloat16, jnp.float32))
            sdt = jnp.asarray(np.log1p(np.exp(rng.randn(b, s, h) - 2.0)),
                              jnp.float32)
            s_a = jnp.asarray(np.log(rng.uniform(1.0, 16.0, h)), jnp.float32)
            sb, sc = (jnp.asarray(0.3 * rng.randn(b, s, groups, state),
                                  jnp.bfloat16) for _ in range(2))

            def scan_and_cotangents(fn, chunk=chunk):
                def run(x, dt, a_log, bm, cm, g):
                    y, vjp = jax.vjp(lambda *a: fn(*a, chunk), x, dt, a_log,
                                     bm, cm)
                    return [leaf.astype(jnp.float32)
                            / jnp.max(jnp.abs(leaf.astype(jnp.float32)))
                            for leaf in (y, *vjp(g))]
                return run

            compare(name, scan_and_cotangents(_ssd_kernels),
                    scan_and_cotangents(_ssd), (sx, sdt, s_a, sb, sc, sg),
                    atol=2e-2)

        # -- the experts' grouped matmul at the widths the compiler's kernel
        # would tile by one lane tile: the product and both cotangents against
        # `ragged_dot` INSIDE the groups (neither side defines a row past
        # them), each over its own largest entry
        m, kk, nn, sizes = shapes["grouped_matmul"]
        gx, gg = (jnp.asarray(rng.randn(m, w), jnp.bfloat16) for w in (kk, nn))
        gw = jnp.asarray(0.02 * rng.randn(len(sizes), kk, nn), jnp.float32)

        def product_and_cotangents(x, w, g):
            held = sum(sizes)
            y, vjp = jax.vjp(lambda x, w: _grouped_matmul(
                x, w, jnp.asarray(sizes, jnp.int32)), x, w)
            dx, dw = vjp(g)
            return [leaf.astype(jnp.float32)
                    / jnp.max(jnp.abs(leaf.astype(jnp.float32)))
                    for leaf in (y[:held], dx[:held], dw)]

        compare("grouped_matmul", product_and_cotangents,
                product_and_cotangents, (gx, gw, gg), atol=2e-2)

        # -- the four registry kernels
        n, d, vocab = shapes["embed_grad"]
        ev = jnp.asarray(rng.randn(n, d).astype(np.float32))
        ei = jnp.asarray((rng.zipf(1.3, n) % vocab).astype(np.int32))
        rows_fn = lambda v, i: embed_grad.embed_grad_rows(v, i, vocab)  # noqa
        compare("fused_embed_grad", rows_fn, rows_fn, (ev, ei), atol=1e-4,
                rtol=1e-5)

        nnz, nrow, kk, f = shapes["csr_spmm"]
        sv = jnp.asarray(rng.randn(nnz).astype(np.float32))
        sr = jnp.asarray(rng.randint(0, nrow, nnz).astype(np.int32))
        sc = jnp.asarray(rng.randint(0, kk, nnz).astype(np.int32))
        sb = jnp.asarray(rng.randn(kk, f).astype(np.float32))
        spmm = lambda v, r, c, b: csr_spmm.coo_matmat(v, r, c, nrow, b)  # noqa
        compare("csr_spmm", spmm, spmm, (sv, sr, sc, sb), atol=1e-4,
                rtol=1e-5)

        nq, block = shapes["quant"]
        qx = jnp.asarray(rng.randn(nq).astype(np.float32))
        compare("quant_blocks",
                lambda x: quant_comm.quantize_blocks(x, block, "int8")[:2],
                lambda x: comm_quant.quantize_blocks(x, block, "int8")[:2],
                (qx,), atol=0, exact=True)
        qq, qs, _n = comm_quant.quantize_blocks(qx, block, "int8")
        compare("dequant_blocks",
                lambda q, s: quant_comm.dequantize_blocks(q, s, nq, block),
                lambda q, s: comm_quant.dequantize_blocks(q, s, nq, block),
                (qq, qs), atol=0, exact=True)

        class _Opt:
            beta1, beta2, epsilon = 0.9, 0.999, 1e-7
            weight_decay, l2reg = 0.01, 1e-4

        p, g, m = (jnp.asarray(rng.randn(*shapes["opt"]).astype(np.float32))
                   for _ in range(3))
        vv = jnp.abs(jnp.asarray(rng.randn(*shapes["opt"]), jnp.float32))
        adam = lambda p, g, m, v: fused_opt.adam_step(  # noqa: E731
            _Opt, p, g, {"m": m, "v": v, "t": jnp.float32(3.0)}, 0.01)
        compare("fused_adam", adam, adam, (p, g, m, vv), atol=1e-6,
                rtol=1e-6)
        sgd = lambda p, g: fused_opt.sgd_step(_Opt, p, g, 0.1)  # noqa: E731
        compare("fused_sgd", sgd, sgd, (p, g), atol=1e-6, rtol=1e-6)
        if chip:
            _check_fallback_reasons()
    return rec


# ---------------------------------------------------------------------------
# multichip: runs whenever more than one chip is visible
# ---------------------------------------------------------------------------

def phase_multichip(n_devices):
    """(a) the executor phase under AllReduce on the deduced dp mesh,
    (b) the flagship phase on dp2 x tp2 with sharded params and optimizer
    state, (c) dryrun_multichip on the real devices. The registry kernels
    decline inside multi-device programs (docs/KERNELS.md, "Partitioned
    programs"); their counts are printed, not asserted."""
    rec = {}
    with _phase("multichip", rec):
        a = phase_executor(comm_mode="AllReduce", name="multichip:executor")
        _check(a["mesh"] == {"dp": n_devices},
               f"deduced mesh {a['mesh']}, expected dp={n_devices}")
        b = phase_flagship(mesh_axes={"dp": n_devices // 2, "tp": 2},
                           name="multichip:flagship")
        import __graft_entry__ as graft
        graft.dryrun_multichip(n_devices)
        rec.update({"executor_mesh": a["mesh"], "flagship_mesh": b["mesh"],
                    "kernel_dispatch": a["dispatch"]["counts"]})
    return rec


def main():
    global _COUNTER
    device = phase_device()          # exits non-zero off-TPU, first of all
    _COUNTER = _CompileCounter()
    phase_executor()
    _check_fallback_reasons()
    phase_flagship()
    phase_ps()
    _check_fallback_reasons()
    phase_kernels()
    if device["count"] > 1:
        phase_multichip(device["count"])
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
