"""The one vocabulary of names (telemetry/tracing.py, docs/OBSERVABILITY.md):
every Pallas kernel's name and every step's phase scopes are in the lowered
program, and the `hetu.*` spans of `SubExecutor.run` land in any
jax.profiler capture, with no switch, on the profiler's clock."""
import contextlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu.kernels import (csr_spmm, embed_grad, flash_attention,
                              fused_ce, fused_opt, grouped_matmul, quant_comm,
                              registry, rope, ssd)
from hetu_tpu.kernels import gdn as gdn_kernel
from hetu_tpu.kernels import kda as kda_kernel
from hetu_tpu.telemetry import tracing as tr


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _flash_bwd(q, k, v, o, lse, do):
    return flash_attention._bwd_pallas(
        ((q, k, v), o, lse, None), do, n_heads=2, scale=0.125, causal=False,
        block_q=128, block_k=128, interpret=True)


_QKV = (_f32(1, 128, 128),) * 3     # (batch, seq, 2 heads * 64)
# one tile of 128 has `flash_bwd`; two tiles have `flash_bwd_dqkv`
_BWD_1 = _QKV + (_f32(1, 128, 128), _f32(2, 1, 128), _f32(1, 128, 128))
_BWD_2 = (_f32(1, 256, 128),) * 4 + (_f32(2, 1, 256), _f32(1, 256, 128))
_SGD = type("Opt", (), {"l2reg": 0.0})()
_ADAM = type("Opt", (), {"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8,
                         "weight_decay": 0.0})()

# kernel name -> (a program that calls it, its argument shapes)
KERNEL_PROGRAMS = {
    flash_attention.FLASH_FWD: (
        lambda q, k, v: flash_attention.flash_attention_btd(
            (q, k, v), 2, causal=False), _QKV),
    flash_attention.FLASH_BWD: (_flash_bwd, _BWD_1),
    flash_attention.FLASH_BWD_DQKV: (_flash_bwd, _BWD_2),
    fused_ce.FUSED_CE_FWD: (
        fused_ce.fused_linear_nll,
        (_f32(128, 128), _f32(256, 128), _f32(256), _i32(128))),
    fused_ce.FUSED_CE_BWD_DH: (
        jax.grad(lambda h, w, b, t: fused_ce.fused_linear_nll(
            h, w, b, t).sum()),
        (_f32(128, 128), _f32(256, 128), _f32(256), _i32(128))),
    fused_ce.FUSED_CE_BWD_DW: (
        jax.grad(lambda h, w, b, t: fused_ce.fused_linear_nll(
            h, w, b, t).sum(), argnums=1),
        (_f32(128, 128), _f32(256, 128), _f32(256), _i32(128))),
    fused_opt.FUSED_ADAM: (
        lambda p, g, m, v: fused_opt.adam_step(
            _ADAM, p, g, {"m": m, "v": v, "t": jnp.zeros(())}, 1e-3),
        (_f32(1024, 128),) * 4),
    fused_opt.FUSED_SGD: (
        lambda p, g: fused_opt.sgd_step(_SGD, p, g, 0.1),
        (_f32(1024, 128),) * 2),
    embed_grad.FUSED_EMBED_GRAD: (
        lambda vec, idx: embed_grad.embed_grad_rows(vec, idx, 1000),
        (_f32(128, 128), _i32(128))),
    csr_spmm.CSR_SPMM: (
        lambda vals, rows, cols, b: csr_spmm.coo_matmat(vals, rows, cols,
                                                        8, b),
        (_f32(1024), _i32(1024), _i32(1024), _f32(8, 128))),
    rope.ROPE_PAIRS: (
        lambda x: rope.rope_interleaved(x, 0, 1e6, 192, 128),
        (_f32(1, 32, 384),)),
    rope.ROPE_HALVES: (
        lambda x: rope.rope_halves(x, 0, 1e4, 128, 64, at=(128, 256)),
        (_f32(1, 32, 512),)),
    ssd.SSD_FWD: (
        lambda x, dt, bc: ssd.ssd(x, dt, -dt, bc, bc, 128),
        (_f32(1, 128, 2, 64), _f32(1, 128, 2), _f32(1, 128, 1, 128))),
    ssd.SSD_BWD: (
        jax.grad(lambda x, dt, bc: ssd.ssd(x, dt, -dt, bc, bc, 128).sum()),
        (_f32(1, 128, 2, 64), _f32(1, 128, 2), _f32(1, 128, 1, 128))),
    kda_kernel.KDA_FWD: (
        lambda x, g, beta: kda_kernel.kda(x, x, x, g, beta, 64),
        (_f32(1, 128, 2, 128), _f32(1, 128, 2, 128), _f32(1, 128, 2))),
    kda_kernel.KDA_BWD: (
        jax.grad(lambda x, g, beta: kda_kernel.kda(
            x, x, x, g, beta, 64).sum()),
        (_f32(1, 128, 2, 128), _f32(1, 128, 2, 128), _f32(1, 128, 2))),
    gdn_kernel.GDN_FWD: (
        lambda x, v, g: gdn_kernel.gdn(x, x, v, g, g, 64),
        (_f32(1, 128, 1, 128), _f32(1, 128, 2, 128), _f32(1, 128, 2))),
    gdn_kernel.GDN_BWD: (
        jax.grad(lambda x, v, g: gdn_kernel.gdn(x, x, v, g, g, 64).sum()),
        (_f32(1, 128, 1, 128), _f32(1, 128, 2, 128), _f32(1, 128, 2))),
    grouped_matmul.GROUPED_MATMUL: (
        grouped_matmul.grouped_matmul,
        (_f32(32, 384), _f32(2, 384, 192), _i32(2))),
    grouped_matmul.GROUPED_MATMUL_DW: (
        jax.grad(lambda w, xs, sizes: grouped_matmul.grouped_matmul(
            xs, w, sizes).sum()),
        (_f32(2, 384, 192), _f32(32, 384), _i32(2))),
    quant_comm.QUANT_BLOCKS: (
        lambda x: quant_comm.quantize_blocks(x, 128, "int8"),
        (_f32(1024),)),
    quant_comm.DEQUANT_BLOCKS: (
        lambda q, s: quant_comm.dequantize_blocks(q, s, 1024, 128),
        (jax.ShapeDtypeStruct((1024,), jnp.int8), _f32(8))),
}


def _lowered(fn, shapes):
    with registry.active("force"):
        return jax.jit(fn).lower(*shapes).as_text(debug_info=True)


@pytest.mark.parametrize("name", sorted(KERNEL_PROGRAMS))
def test_kernel_name_in_lowered_program(name):
    """Each pallas_call site passes `name=`: jax wraps the call in
    named_scope(name), XLA names the instruction after it, and the device
    trace shows the kernel under that name."""
    fn, shapes = KERNEL_PROGRAMS[name]
    # under jvp/transpose the scope is wrapped (`transpose(jvp(<name>))`):
    # readers match a kernel by substring
    assert re.search(rf'"[^"]*\b{name}\b[^"]*/pallas_call"',
                     _lowered(fn, shapes))
    # reduce/trace.py:family strips a trailing .<digits> or _<digits>
    assert not name[-1].isdigit()


# -- phase scopes in the compiled program --------------------------------------

def _tiny_bert():
    from hetu_tpu.models import bert
    cfg = bert.BertConfig.hf(vocab_size=128, d_model=32, n_heads=2,
                             n_layers=2, d_ff=64, max_seq_len=16,
                             type_vocab_size=2, dtype=jnp.bfloat16)
    params = bert.init_params(jax.random.PRNGKey(0), cfg)
    B, T, P = 2, 16, 4
    batch = {"input_ids": np.zeros((B, T), np.int32),
             "input_mask": np.ones((B, T), np.int32),
             "segment_ids": np.zeros((B, T), np.int32),
             "mlm_positions": np.zeros((B, P), np.int32),
             "mlm_ids": np.zeros((B, P), np.int32),
             "mlm_weights": np.ones((B, P), np.float32),
             "nsp_label": np.zeros((B,), np.int32),
             "label": np.zeros((B,), np.int32)}
    return bert, cfg, params, batch


def _lower_bert_pretrain():
    bert, cfg, params, batch = _tiny_bert()
    batch.pop("label")
    return bert.make_pretrain_step(cfg).lower(
        params, bert.init_opt_state(params), batch)


def _lower_bert_finetune():
    bert, cfg, params, batch = _tiny_bert()
    params = bert.init_classifier_params(jax.random.PRNGKey(1), cfg, 2,
                                         pretrained=params)
    return bert.make_finetune_step(cfg).lower(
        params, bert.init_opt_state(params), batch)


def _lower_transformer():
    from hetu_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                n_layers=2, d_ff=64, max_seq_len=16)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tok = np.zeros((2, 16), np.int32)
    return tfm.make_train_step(cfg).lower(
        params, tfm.init_opt_state(params), tok, tok)


def _tiny_mlp(bs=16):
    """test_telemetry's tiny-MLP Executor, telemetry off, and one step."""
    from test_telemetry import _feeds, _tiny_mlp as graph
    x, y_, loss, train_op = graph(ht)
    ex = ht.Executor({"train": [loss, train_op]}, ctx=ht.cpu(0), seed=0)
    rng = np.random.RandomState(0)

    def step():
        xv, yv = _feeds(rng, bs)
        return ex.run("train", feed_dict={x: xv, y_: yv})
    return ex, step


def _lower_executor():
    ex, step = _tiny_mlp()
    step()
    fn, args = ex.subexecutors["train"]._last_call
    return fn.lower(*args)


@pytest.mark.parametrize("lower, fwd", [
    (_lower_bert_pretrain, tr.SCOPE_FWD),
    (_lower_bert_finetune, tr.SCOPE_FWD),
    (_lower_transformer, tr.SCOPE_FWD),
    # the Executor's forward and backward carry jax's own marks (GradientOp
    # is a jax.vjp); each optimizer op sits one segment below hetu_opt
    (_lower_executor, ""),
], ids=["bert_pretrain", "bert_finetune", "transformer", "executor"])
def test_phase_scopes_in_lowered_step(lower, fwd):
    names = set(re.findall(r'loc\("([^"]+)"', lower().as_text(
        debug_info=True)))
    assert any(f"/{tr.SCOPE_OPT}/" in n for n in names)
    assert any(f"/jvp({fwd}" in n for n in names)
    assert any(f"/transpose(jvp({fwd}" in n for n in names)
    # an op is in one phase: nothing is both optimizer and backward work
    assert not any(tr.SCOPE_OPT in n and "transpose(" in n for n in names)
    if not fwd:
        assert any(re.search(rf"/{tr.SCOPE_OPT}/Optimizer_\w+/", n)
                   for n in names)


# -- host spans in a capture ---------------------------------------------------

def _steps_with_children(spans):
    steps = [s for s in spans if s[0] == tr.STEP]
    return [(s, [c for c in spans if c[0] in tr.STEP_SPANS
                 and s[1] <= c[1] and c[2] <= s[2]]) for s in steps]


def test_capture_holds_each_step_and_its_children(tmp_path):
    """Any jax.profiler capture, opened by anyone, holds one hetu_step a run
    call and its hetu.* children in table order: no switch, no telemetry."""
    from conftest import read_hetu_spans
    ex, step = _tiny_mlp(bs=4096)
    assert ex.telemetry is None
    for _ in range(2):
        step()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            step()
    finally:
        jax.profiler.stop_trace()
    steps = _steps_with_children(read_hetu_spans(str(tmp_path)))
    assert [s[3]["step_num"] for s, _c in steps] == [2, 3, 4]
    for i, (s, children) in enumerate(steps):
        assert [c[0] for c in children] == list(tr.STEP_SPANS)
        covered = sum(c[2] - c[1] for c in children)
        # the capture's first step also pays the profiler's own start-up
        # between the spans (its Python tracer hooks every call)
        assert covered >= (0.9 if i else 0.75) * (s[2] - s[1]), (covered, s)
        assert not any("compiled" in c[3] for c in children)
    # the stamps the other consumers read are taken only when one is on
    assert ex.subexecutors["train"].last_phases is None


def test_xla_trace_window_opens_with_telemetry_off(tmp_path, monkeypatch):
    """HETU_XLA_TRACE=dir:2:3 captures steps 2-4 whole, children and all,
    with telemetry off."""
    from conftest import read_hetu_spans
    from hetu_tpu import telemetry
    telemetry.shutdown()
    monkeypatch.delenv("HETU_TELEMETRY", raising=False)
    monkeypatch.setenv("HETU_XLA_TRACE", f"{tmp_path}:2:3")
    ex, step = _tiny_mlp()
    assert ex.telemetry is None and ex.xla_window is not None
    for _ in range(7):
        step()
    assert ex.xla_window._done       # closed at step 5, not at close()
    ex.close()
    steps = _steps_with_children(read_hetu_spans(str(tmp_path)))
    assert [s[3]["step_num"] for s, _c in steps] == [2, 3, 4]
    for _s, children in steps:
        assert [c[0] for c in children] == list(tr.STEP_SPANS)


def test_build_span_marks_a_compile(tmp_path):
    from conftest import read_hetu_spans
    ex, step = _tiny_mlp()
    jax.profiler.start_trace(str(tmp_path))
    try:
        step()
        step()
    finally:
        jax.profiler.stop_trace()
    builds = [s for s in read_hetu_spans(str(tmp_path))
              if s[0] == tr.BUILD]
    assert [b[3].get("compiled") for b in builds] == [1, None]


def test_timed_step_takes_its_stamps_from_the_spans(tmp_path, monkeypatch):
    """With telemetry on, last_phases comes from the same spans: one
    delimitation of each phase, two readers."""
    from hetu_tpu import telemetry
    telemetry.shutdown()
    monkeypatch.setenv("HETU_TELEMETRY_DIR", str(tmp_path))
    x = ht.Variable(name="x", trainable=False)
    w = ht.Variable("wspan", value=np.ones((3, 2), np.float32))
    ex = ht.Executor([ht.matmul_op(x, w)], ctx=ht.cpu(0),
                     telemetry="metrics")
    try:
        stamped = []
        real = tr._Stamped.__exit__

        def spy(self, *exc):
            stamped.append(self._name)
            return real(self, *exc)
        monkeypatch.setattr(tr._Stamped, "__exit__", spy)
        ex.run("default", feed_dict={x: np.ones((4, 3), np.float32)})
        assert stamped == list(tr.STEP_SPANS) + [tr.STEP]
        phases = ex.subexecutors["default"].last_phases
        assert phases["step_ms"] >= phases["dispatch_ms"] > 0
        assert phases["compile_ms"] > 0 and phases["prestep_ms"] >= 0
    finally:
        telemetry.shutdown()


# -- the trunk's parts: block, embedding, head and scan scopes (PR 34) ---------

NEW_SCOPES = tr.BLOCK_SCOPES + (tr.SCOPE_EMBED, tr.SCOPE_HEAD) + tr.SSD_SCOPES
OLD_NAMES = (
    (tr.STEP, tr.SCOPE_FWD, tr.SCOPE_OPT, tr.SCOPE_EXIT) + tr.MOE_SCOPES
    + tr.SSM_SCOPES + tr.STEP_SPANS
    + tuple(n for pair in tr.REMAT_CANDIDATES for n in pair)
    + tuple(KERNEL_PROGRAMS))

_MLP = (tr.SCOPE_BLK_MLP_UP, tr.SCOPE_BLK_MLP_DOWN)
# dialect -> the block scopes its layers' `jax.checkpoint` recomputes (every
# applicable one), the scopes outside the layers, and the older scopes the
# new ones must sit beside
DIALECTS = {
    "bert": (tr.BLOCK_SCOPES, (tr.SCOPE_EMBED, tr.SCOPE_HEAD), ()),
    "olmoe": (tuple(s for s in tr.BLOCK_SCOPES if s not in _MLP),
              (tr.SCOPE_EMBED, tr.SCOPE_HEAD), tr.MOE_SCOPES),
    "ouro": (tr.BLOCK_SCOPES, (tr.SCOPE_EMBED, tr.SCOPE_HEAD),
             (tr.SCOPE_EXIT,)),
    "granite": (tr.BLOCK_SCOPES + tr.SSD_SCOPES,
                (tr.SCOPE_EMBED, tr.SCOPE_HEAD), tr.SSM_SCOPES),
}


def _lower_dialect(which):
    """The train step of one small model of the dialect, lowered."""
    from hetu_tpu.models import (hf_granite, hf_olmoe, hf_ouro,
                                 transformer as tfm)
    # the small published-shape configs test_granite_model.py trains on
    from test_granite_model import HF, OLMOE_HF, OURO_HF
    if which == "bert":
        return _lower_bert_pretrain()
    cfg = {"olmoe": lambda: hf_olmoe.config_from_hf(OLMOE_HF),
           "ouro": lambda: hf_ouro.config_from_hf(OURO_HF),
           "granite": lambda: hf_granite.config_from_hf(HF)}[which]()
    params = jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    opt = jax.eval_shape(tfm.init_opt_state, params)
    return tfm.make_train_step(cfg).lower(params, opt, _i32(2, 32),
                                          _i32(2, 32))


def _segments(op_name):
    return [re.sub(r"^(?:\w+\()+|\)+$", "", s) for s in op_name.split("/")]


def test_new_scope_names_are_documented_and_collide_with_no_old_name():
    """Each constant is a row of docs/OBSERVABILITY.md; no new name is a
    substring of an old one or contains one (reduce/inside.py, moe.py,
    ssm.py and loop.py match by substring), nor of another new one."""
    doc = open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "OBSERVABILITY.md")).read()
    assert len(set(NEW_SCOPES)) == len(NEW_SCOPES) == 11
    for new in NEW_SCOPES:
        assert f"`{new}`" in doc, new
        for old in OLD_NAMES:
            assert new not in old and old not in new, (new, old)
        for other in NEW_SCOPES:
            assert other == new or new not in other, (new, other)


@pytest.mark.parametrize("which", sorted(DIALECTS))
def test_block_scopes_in_the_compiled_step(which):
    """The compiled step's `op_name` paths carry each applicable scope as a
    plain segment in forward, recomputed (`rematted_computation`) and
    backward (`transpose(`) form; the new scopes nest where the older ones
    already stood (what benchmark/reduce/block.py reads)."""
    in_block, outside, older = DIALECTS[which]
    text = _lower_dialect(which).compile().as_text()
    names = set(re.findall(r'op_name="([^"]+)"', text))
    paths = {n: _segments(n) for n in names if tr.SCOPE_FWD in n}

    def forms(scope):
        under = [n for n, segs in paths.items() if scope in segs]
        return (any("transpose(" not in n for n in under),
                any("rematted_computation" in n for n in under),
                any("transpose(" in n and "rematted_computation" not in n
                    for n in under))

    for scope in in_block:
        # a pre-LN block's last matmul feeds nothing its backward pass
        # reads: the compiler drops its recomputation (sandwich and post-LN
        # norms read it, so BERT and Ouro run `w2` again)
        again = not (which == "granite" and scope == tr.SCOPE_BLK_MLP_DOWN)
        assert forms(scope) == (True, again, True), scope
    for scope in outside:
        # outside the layers' checkpoint: nothing is recomputed
        assert forms(scope) == (True, False, True), scope
    for scope in older:
        assert any(scope in segs for segs in paths.values()), scope
    for scope in set(NEW_SCOPES) - set(in_block) - set(outside):
        assert not any(scope in segs for segs in paths.values()), scope
    # nothing of the optimizer is under a part's name
    assert not [n for n in names if tr.SCOPE_OPT in n
                and set(_segments(n)) & set(NEW_SCOPES)]
    if which == "ouro":
        # the head passes are the exit head's: hetu_exit/hetu_head/...
        head = [segs for segs in paths.values() if tr.SCOPE_HEAD in segs]
        assert head and all(
            tr.SCOPE_EXIT in segs
            and segs.index(tr.SCOPE_EXIT) < segs.index(tr.SCOPE_HEAD)
            for segs in head)
    if which == "granite":
        for segs in paths.values():
            for scope in set(tr.SSD_SCOPES) & set(segs):
                assert tr.SCOPE_SSM_SCAN in segs[:segs.index(scope)], segs
    if which == "olmoe":
        # a MoE block keeps its four scopes
        assert not any(set(_MLP) & set(segs) for segs in paths.values())


@pytest.mark.parametrize("which", sorted(DIALECTS))
def test_the_scopes_leave_the_lowered_program_as_it_was(which, monkeypatch):
    """Scopes are locations: with each new scope patched to a
    `contextlib.nullcontext` the step lowers to the same StableHLO, byte for
    byte, once locations are stripped (`as_text()` without debug info). The
    program did not change, so no end-to-end metric can."""
    with_scopes = _lower_dialect(which)
    assert any(s in with_scopes.as_text(debug_info=True) for s in NEW_SCOPES)
    real = jax.named_scope
    monkeypatch.setattr(
        jax, "named_scope",
        lambda name: (contextlib.nullcontext() if name in NEW_SCOPES
                      else real(name)))
    without = _lower_dialect(which)
    assert not any(s in without.as_text(debug_info=True) for s in NEW_SCOPES)
    assert without.as_text() == with_scopes.as_text()


def test_a_compile_cache_serves_the_names_it_was_filled_with(tmp_path):
    """The trap docs/OBSERVABILITY.md warns of: jax's persistent cache keys
    a program AFTER stripping debug info, and a named scope is debug info.
    A program compiled without a scope and then WITH one against the same
    cache directory is one cache entry, and the second load carries the
    first's `op_name`s: the new scope is absent from the executable (and so
    from a trace) until the cache is cleared."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    def program(scope):
        def f(x, w):
            with scope():
                y = jnp.dot(x, w)
            return jnp.tanh(y).sum()
        return jax.jit(f).lower(_f32(8, 16), _f32(16, 4))

    named = lambda: jax.named_scope(tr.SCOPE_BLK_QKV)
    old = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs,
           jax.config.jax_persistent_cache_min_entry_size_bytes)
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        cc.reset_cache()
        bare = program(contextlib.nullcontext)
        scoped_ = program(named)
        # the same program once locations are stripped, another with them
        assert bare.as_text() == scoped_.as_text()
        assert tr.SCOPE_BLK_QKV in scoped_.as_text(debug_info=True)
        first = bare.compile().as_text()
        entries = sorted(p.name for p in tmp_path.iterdir()
                         if not p.name.endswith("-atime"))
        assert len(entries) == 1
        second = scoped_.compile().as_text()
        assert sorted(p.name for p in tmp_path.iterdir()
                      if not p.name.endswith("-atime")) == entries
        assert tr.SCOPE_BLK_QKV not in second
        assert (re.findall(r'op_name="([^"]+)"', second)
                == re.findall(r'op_name="([^"]+)"', first))
        # a cleared cache compiles the names in
        for p in tmp_path.iterdir():
            p.unlink()
        cc.reset_cache()
        assert tr.SCOPE_BLK_QKV in program(named).compile().as_text()
    finally:
        jax.config.update("jax_compilation_cache_dir", old[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs", old[1])
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", old[2])
        cc.reset_cache()
