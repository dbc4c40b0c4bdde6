"""BERT encoder pretraining: bidirectionality, padding-mask correctness,
MLM+NSP training, data-pipeline integration, and dp/tp sharding parity
(single-device oracle vs 8-device mesh — SURVEY.md §4's oracle strategy).
"""
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from hetu_tpu.models import bert
from hetu_tpu.parallel.mesh import auto_mesh

TINY = bert.BertConfig(vocab_size=96, d_model=32, n_heads=4, n_layers=2,
                       d_ff=64, max_seq_len=32, dtype=jnp.float32,
                       remat=False)


def _rand_batch(rng, cfg, B=4, T=16, P=4, pad_from=None):
    ids = rng.randint(3, cfg.vocab_size, (B, T)).astype(np.int32)
    mask = np.ones((B, T), np.int32)
    if pad_from is not None:
        mask[:, pad_from:] = 0
    pos = np.stack([rng.choice(np.arange(1, T if pad_from is None else
                                         pad_from), P, replace=False)
                    for _ in range(B)]).astype(np.int32)
    return {"input_ids": ids, "input_mask": mask,
            "segment_ids": (np.arange(T)[None, :] >= T // 2)
                           .astype(np.int32).repeat(B, 0),
            "mlm_positions": pos,
            "mlm_ids": rng.randint(3, cfg.vocab_size, (B, P)).astype(np.int32),
            "mlm_weights": np.ones((B, P), np.float32),
            "nsp_label": rng.randint(0, 2, (B,)).astype(np.int32)}


def test_encoder_is_bidirectional():
    """A LATER token must change the hidden state at an EARLIER position —
    the defining difference from the causal flagship trunk."""
    params = bert.init_params(jax.random.PRNGKey(0), TINY)
    rng = np.random.RandomState(0)
    b = _rand_batch(rng, TINY)
    h1 = bert.encode(params, b["input_ids"], b["segment_ids"], TINY)
    ids2 = b["input_ids"].copy()
    ids2[:, -1] = (ids2[:, -1] + 1) % TINY.vocab_size
    h2 = bert.encode(params, ids2, b["segment_ids"], TINY)
    # earlier positions see the change
    assert float(jnp.max(jnp.abs(h1[:, 0] - h2[:, 0]))) > 1e-6


def test_padding_mask_blocks_pad_keys():
    """Garbage in padded slots must not leak into real positions' outputs."""
    params = bert.init_params(jax.random.PRNGKey(0), TINY)
    rng = np.random.RandomState(1)
    b = _rand_batch(rng, TINY, pad_from=10)
    h1 = bert.encode(params, b["input_ids"], b["segment_ids"], TINY,
                     input_mask=b["input_mask"])
    ids2 = b["input_ids"].copy()
    ids2[:, 10:] = 7   # different pad garbage
    h2 = bert.encode(params, ids2, b["segment_ids"], TINY,
                     input_mask=b["input_mask"])
    np.testing.assert_allclose(np.asarray(h1[:, :10]),
                               np.asarray(h2[:, :10]), atol=1e-5)
    # and WITHOUT the mask the garbage does leak (the test is non-vacuous)
    h3 = bert.encode(params, b["input_ids"], b["segment_ids"], TINY)
    h4 = bert.encode(params, ids2, b["segment_ids"], TINY)
    assert float(jnp.max(jnp.abs(h3[:, :10] - h4[:, :10]))) > 1e-6


def test_mlm_nsp_pretrain_loss_decreases():
    params = bert.init_params(jax.random.PRNGKey(0), TINY)
    opt = bert.init_opt_state(params)
    step = bert.make_pretrain_step(TINY, lr=3e-3)
    rng = np.random.RandomState(2)
    b = _rand_batch(rng, TINY)   # one fixed batch: must be memorizable
    first = None
    for i in range(40):
        loss, (mlm, nsp), params, opt = step(params, opt, b)
        if i == 0:
            first = float(loss)
    assert np.isfinite(first)
    assert float(loss) < 0.3 * first, (first, float(loss))
    assert float(mlm) >= 0 and float(nsp) >= 0


def test_pipeline_to_pretrain_step():
    """End-to-end: WordPiece tokenizer -> sentence-pair instances -> batch ->
    one fused pretrain step."""
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "examples", "nlp"))
    import processBertData as pbd
    from hetu_tpu.tokenizers import BertTokenizer

    words = ["the", "cat", "sat", "on", "mat", "dog", "ran", "fast",
             "##s", "a"]
    vocab = {t: i for i, t in enumerate(
        ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]"] + words)}
    tok = BertTokenizer(vocab)
    sentences = ["the cat sat on a mat", "a dog ran fast",
                 "the dog sat", "a cat ran", "the mat ran fast"]
    inst = pbd.create_instances_from_document(
        sentences, tok, max_seq_length=24, max_predictions_per_seq=4)
    assert len(inst) >= 2
    cfg = bert.BertConfig(vocab_size=len(vocab), d_model=16, n_heads=2,
                          n_layers=2, d_ff=32, max_seq_len=24,
                          dtype=jnp.float32, remat=False)
    batch = bert.batch_from_instances(inst)
    params = bert.init_params(jax.random.PRNGKey(0), cfg)
    step = bert.make_pretrain_step(cfg, lr=1e-3)
    loss, (mlm, nsp), params, _ = step(params, bert.init_opt_state(params),
                                       batch)
    assert np.isfinite(float(loss)) and float(mlm) > 0


def test_bert_trainer_example_end_to_end(tmp_path, capsys):
    """examples/nlp/train_hetu_bert.py: corpus -> tokenizer -> instances ->
    pretrain loop -> checkpoint -> RESUME, losses improving."""
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "examples", "nlp"))
    import train_hetu_bert
    ck = str(tmp_path / "ck")
    first = train_hetu_bert.main(["--num-epoch", "3", "--cpu",
                                  "--ckpt-dir", ck])
    resumed = train_hetu_bert.main(["--num-epoch", "6", "--cpu",
                                    "--ckpt-dir", ck, "--resume"])
    out = capsys.readouterr().out
    # the restore branch actually fired and only epochs 3-5 were trained
    assert "resumed from epoch 2" in out
    assert out.count("epoch 0:") == 1   # first run only
    assert np.isfinite(first) and np.isfinite(resumed)
    assert resumed < first   # kept learning across the resume


def test_dp_tp_sharded_step_matches_single_device():
    """BERT-base-shaped step on a dp4 x tp2 mesh == unsharded oracle."""
    mesh = auto_mesh(8, tp=2)
    params = bert.init_params(jax.random.PRNGKey(0), TINY)
    opt = bert.init_opt_state(params)
    rng = np.random.RandomState(3)
    b = _rand_batch(rng, TINY, B=8)

    ref_step = bert.make_pretrain_step(TINY, lr=1e-3)
    ref_loss, _, ref_params, _ = ref_step(
        jax.tree.map(jnp.copy, params), jax.tree.map(jnp.copy, opt), b)

    step = bert.make_pretrain_step(TINY, mesh=mesh, lr=1e-3)
    loss, _, new_params, _ = step(params, opt, b)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    # dense packed batch (no input_mask key) must work sharded too — the
    # prefix sharding covers whatever keys the batch has
    dense = {k: v for k, v in _rand_batch(
        np.random.RandomState(4), TINY, B=8).items() if k != "input_mask"}
    dp = bert.init_params(jax.random.PRNGKey(1), TINY)
    dloss, _, _, _ = step(dp, bert.init_opt_state(dp), dense)
    assert np.isfinite(float(dloss))
    for k in ("embed", "mlm_dense", "nsp_w"):
        np.testing.assert_allclose(np.asarray(new_params[k]),
                                   np.asarray(ref_params[k]), atol=1e-5)


def test_fused_mlm_ce_matches_materializing_form():
    """The fused Pallas linear+CE MLM loss (default on the single-program
    path) must equal the logits-materializing einsum form — loss AND
    gradients."""
    import dataclasses
    params = bert.init_params(jax.random.PRNGKey(0), TINY)
    b = _rand_batch(np.random.RandomState(11), TINY, B=4)
    on = dataclasses.replace(TINY, fused_mlm_ce=True)   # force off-TPU
    off = dataclasses.replace(TINY, fused_mlm_ce=False)

    # loss, its parts and `jax.grad` in one compiled program a form: run
    # eagerly, each is compiled op by op, the gradient a second time
    def loss_and_grads(cfg):
        return jax.jit(jax.value_and_grad(
            lambda p: bert.pretrain_loss(p, b, cfg), has_aux=True))(params)

    (lf, (mf, _)), gf = loss_and_grads(on)
    (lo, (mo, _)), go = loss_and_grads(off)
    assert float(lf) == pytest.approx(float(lo), rel=1e-5)
    assert float(mf) == pytest.approx(float(mo), rel=1e-5)
    for k in ("embed", "mlm_dense", "mlm_bias", "mlm_ln_scale"):
        np.testing.assert_allclose(np.asarray(gf[k]), np.asarray(go[k]),
                                   rtol=2e-4, atol=2e-5, err_msg=k)


def test_dp_sp_masked_step_matches_single_device():
    """Sequence-parallel BERT: on a dp2 x sp2 x tp2 mesh 'auto' resolves to
    RING attention, and a PADDED batch rides the ring as a rotating per-key
    bias — the sharded masked step must equal the unsharded oracle."""
    from hetu_tpu.models import transformer as tfm

    mesh = auto_mesh(8, sp=2, tp=2)
    assert tfm._resolve_attn_impl(TINY.trunk(), mesh, 16,
                                  jnp.zeros((1, 1, 1, 16))) == "ring"
    params = bert.init_params(jax.random.PRNGKey(0), TINY)
    opt = bert.init_opt_state(params)
    rng = np.random.RandomState(7)
    T = 16
    b = _rand_batch(rng, TINY, B=8, T=T, pad_from=12)  # padded tail

    ref_step = bert.make_pretrain_step(TINY, lr=1e-3)
    ref_loss, _, ref_params, _ = ref_step(
        jax.tree.map(jnp.copy, params), jax.tree.map(jnp.copy, opt), b)

    step = bert.make_pretrain_step(TINY, mesh=mesh, lr=1e-3)
    loss, _, new_params, _ = step(params, opt, b)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-4)
    for k in ("embed", "mlm_dense", "nsp_w"):
        np.testing.assert_allclose(np.asarray(new_params[k]),
                                   np.asarray(ref_params[k]), atol=1e-4)


def test_finetune_classifier_from_pretrained_trunk():
    """Pretrain briefly, transplant the trunk into a classifier, fine-tune
    on a separable task (label = does the sequence contain token 5): the
    classifier must fit it; the MLM/NSP heads are gone from the task
    params."""
    params = bert.init_params(jax.random.PRNGKey(0), TINY)
    opt = bert.init_opt_state(params)
    pstep = bert.make_pretrain_step(TINY, lr=1e-3)
    rng = np.random.RandomState(5)
    for _ in range(3):
        _, _, params, opt = pstep(params, opt, _rand_batch(rng, TINY))

    cparams = bert.init_classifier_params(jax.random.PRNGKey(1), TINY,
                                          n_classes=2, pretrained=params)
    assert "mlm_bias" not in cparams and "nsp_w" not in cparams
    assert "cls_w" in cparams and "blocks" in cparams

    B, T = 16, 16
    ids = rng.randint(6, TINY.vocab_size, (B, T)).astype(np.int32)
    ids[: B // 2, rng.randint(1, T)] = 5          # positives contain token 5
    labels = (ids == 5).any(1).astype(np.int32)
    batch = {"input_ids": ids,
             "segment_ids": np.zeros((B, T), np.int32),
             "label": labels}
    fstep = bert.make_finetune_step(TINY, lr=3e-3)
    copt = bert.init_opt_state(cparams)
    for i in range(60):
        loss, acc, cparams, copt = fstep(cparams, copt, batch)
    assert float(acc) == 1.0, (float(loss), float(acc))
    # donation of the task params must NOT have invalidated the pretrained
    # tree (init_classifier_params deep-copies reused leaves)
    h = bert.encode(params, batch["input_ids"], batch["segment_ids"], TINY)
    assert np.isfinite(float(jnp.sum(h)))
