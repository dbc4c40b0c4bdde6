"""The flagship cells stay what they were: ONE recipe, ONE loader map and ONE
table of what each cell's train step lowers to. A refactor that means to
change no program proves it here; a PR that adds a cell adds ONE line,
its digest computed at that PR's PARENT by `_cell_digest`, in a process of
its own (from the root of a checkout of the parent: `JAX_PLATFORMS=cpu
PYTHONPATH=.:tests python -c "import conftest, test_cell_digests as t;
print(t._cell_digest(config, traffic))"`).

The file shares no compiled program with any test: `_cell_digest` has to
call `jax.clear_caches()`, and a file is xdist's unit."""
import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

from hetu_tpu.models import (bert, hf_deepseek_v3, hf_granite, hf_keye,
                             hf_kimi_linear, hf_laguna, hf_lfm2,
                             hf_nemotron_h, hf_olmoe, hf_ouro,
                             hf_qwen3_next, hf_smallthinker,
                             transformer as tfm)
from model_harness import ROOT

LOADERS = {"olmoe-1b-7b": hf_olmoe, "ouro-2.6b": hf_ouro,
           "granite-4.0-h-micro": hf_granite, "lfm2-8b-a1b": hf_lfm2,
           "kanana-2-30b-a3b": hf_deepseek_v3,
           "keye-vl-2.0-30b-a3b": hf_keye, "laguna-xs.2": hf_laguna,
           "nemotron-twotower-30b-a3b": hf_nemotron_h,
           "smallthinker-21b-a3b": hf_smallthinker,
           "kimi-linear-48b-a3b": hf_kimi_linear,
           "qwen3-next-80b-a3b": hf_qwen3_next}

# (sha256[:16] of the LOWERED train step at the cell's own config and traffic
# shapes with the counters cut off private symbols, its lines; sha256[:16] of
# the parameter tree's shapes). BERT and the six older decoder cells were
# computed at the PARENT of ISSUE 49 (commit d8625bc): the first four read
# what they read at the parent of ISSUE 37, lfm2's what it read at ISSUE
# 39's and kanana's what it read at ISSUE 44's. laguna's is of ISSUE 55's
# parent (commit 4414f1f), nemotron's of ISSUE 60's (commit e55f412). No PR
# since has changed what any of them lowers to. The digests are of the CPU's
# lowering, where the `dot` path stands for the kernels; the kernels' own are
# `test_flash_compile_v5e.py::test_many_tile_kernels_lower_to_what_they_were`.
# BERT's other two cells are this program at other shapes. BERT's step was
# pinned again ONCE, by ISSUE 62, which changed it on purpose (`_gelu`'s exact
# form: one float32 `erf` where `erfc` was; 5dd9818f8e559ca8, 2401 lines at
# its parent fb731aa; the parameter tree's digest did not move): the other
# eight lines, unedited, say that no other cell reaches `_gelu`. And ONCE more
# by ISSUE 65, on purpose again (`_gelu`'s exact form under its own
# derivative rule, which ends in a barrier over (u, GELU'), so that the
# backward half evaluates `erf` once: 74ac9d1c59c8f61d, 2415 lines at its
# parent feaa9f6; the tree's digest did not move): again the other lines,
# smallthinker's with them, are unedited.
# smallthinker's is of ISSUE 63's own tree, the PR that added the cell (its
# parent, 43cc042, has no loader for it): the nine lines above it, unedited,
# say that `Router.input`, `mlp="reglu"` and the window kind's own rotary
# flag changed no program that existed. ISSUE 64 (the selection's packed bits
# kept by name, `tracing.REMAT_DSA_MASK`) expected to pin keye's line again
# and did not have to: the CPU reports no limit, so the step lowered here
# takes the bare checkpoint, and a `checkpoint_name` lowers to nothing
# (fc6934dddd62afe8, 6636 lines on both sides). What the name changes where a
# limit is reported is held by `test_keye_model.py::
# test_indexer_loss_and_every_gradient_under_remat`, in the jaxpr.
# kimi-linear's is of ISSUE 66's own tree, the PR that added the cell (its
# parent, 77d889e, has no loader for it): the ten lines above it, unedited,
# kanana's among them, say that the kind "kda", `MLAConfig.rotate` and the
# loaders' shared helpers (`hf_common`) changed no program that existed.
# qwen3-next's is of ISSUE 68's own tree, the PR that added the cell (its
# parent, 0af66c2, has no loader for it): the eleven lines above it,
# unedited, kimi's, laguna's and olmoe's among them, say that the kind
# "gdn", `kda.scan`'s broadcast of a head's decay and its `scope=`,
# `norm_offset`, the gate a column, `shared_gate` and `Router.loss_weights`
# changed no program that existed. ISSUE 69 (Gated DeltaNet's scan as its own
# Mosaic kernels, `kernels/gdn.py`) renewed qwen3-next's line alone, from
# 4ec10b2447249c84 at the same 10,898 lines: on the CPU the kernels' rule
# refuses and the XLA form runs as before, and the repeat of the key heads
# moved from `_gdn_inputs` into `kda.scan`'s XLA route (under the scan's
# scope, after the cast); the eleven lines above it, unedited, kimi's among
# them, say that `scan`'s channel branch and every shared helper lower to
# what they lowered to.
PARENT = {
    ("bert-base", "pretrain-seq512"):
        (("9e2f27a018dc2806", 2426), "0ca3cf6cdc80eded"),
    ("olmoe-1b-7b", "pretrain-seq4096"):
        (("c1c73dbf0bed4d29", 2575), "c2ddcd977285a1b3"),
    ("ouro-2.6b", "pretrain-seq4096-b1"):
        (("1e39cfb68388bbb3", 2434), "bdd3f4f2570a57aa"),
    ("granite-4.0-h-micro", "pretrain-seq8192-b1"):
        (("fbbf3f6e6fc2fcb1", 3519), "68b156d54bca4aa7"),
    ("lfm2-8b-a1b", "pretrain-seq8192-ep4load"):
        (("8c8e334e63485216", 7026), "df8cd1acd6687a54"),
    ("kanana-2-30b-a3b", "pretrain-seq8192-ep8share"):
        (("aabae1f6455f1620", 6215), "c0f6aef309cbdd46"),
    ("keye-vl-2.0-30b-a3b", "pretrain-seq16384-ep8share"):
        (("fc6934dddd62afe8", 6636), "c287774ff5bcf2b1"),
    ("laguna-xs.2", "pretrain-seq16384-b1-ep8share"):
        (("fc81640f06f3be0d", 10347), "6cc57da7b68d31b8"),
    ("nemotron-twotower-30b-a3b", "pretrain-seq8192-b1-ep16share"):
        (("75d12ffe4f5aeefc", 14469), "de05633a1305e7f2"),
    ("smallthinker-21b-a3b", "pretrain-seq16384-b1-ep4share"):
        (("d322fa2782e4e0b8", 6153), "ae3edc310cab3dd8"),
    ("kimi-linear-48b-a3b", "pretrain-seq16384-b1-ep32share"):
        (("1af449db85b38743", 18938), "58d7205c4347be9c"),
    ("qwen3-next-80b-a3b", "pretrain-seq16384-b1-ep16share"):
        (("0d199a3d40944a45", 10898), "a40c3da188d4d176"),
}


def _cell_step(config, traffic):
    """(the cell's LOWERED train step as text, its parameter tree's shapes)"""
    with open(os.path.join(ROOT, "benchmark/configs", config,
                           "config.json")) as f:
        c = json.load(f)
    with open(os.path.join(ROOT, "benchmark/traffic", traffic + ".json")) as f:
        t = json.load(f)
    B, T = t["sequences"], t["seq_len"]
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    # what a fresh process lowers: jax emits a jitted helper it has traced
    # before (`_where`, `_roll_static`) under another private name and
    # another count of them, so the text would follow the cases that ran
    # earlier in this worker (kanana's step read 6,223 lines for 6,215 after
    # the rest of test_keye_model.py: the failure ISSUE 49 found)
    jax.clear_caches()
    if config == "bert-base":
        cfg = bert.BertConfig.hf(
            vocab_size=c["vocab_size"], d_model=c["hidden_size"],
            n_heads=c["num_attention_heads"],
            n_layers=c["num_hidden_layers"], d_ff=c["intermediate_size"],
            max_seq_len=c["max_position_embeddings"],
            type_vocab_size=c["type_vocab_size"], dtype=jnp.bfloat16)
        params = jax.eval_shape(
            lambda: bert.init_params(jax.random.PRNGKey(0), cfg))
        opt = jax.eval_shape(bert.init_opt_state, params)
        P = t["predictions"]
        batch = {"input_ids": i32(B, T), "segment_ids": i32(B, T),
                 "input_mask": f32(B, T), "mlm_positions": i32(B, P),
                 "mlm_ids": i32(B, P), "mlm_weights": f32(B, P),
                 "nsp_label": i32(B)}
        text = bert.make_pretrain_step(cfg, lr=1e-4).lower(
            params, opt, batch).as_text()
    else:
        # the bias's rate where the cell's file gives one (lfm2 and later)
        rate = c.get("assumed", {}).get("expert_bias_update_rate")
        cfg = LOADERS[config].config_from_hf(
            c, dtype=jnp.bfloat16,
            **({} if rate is None else {"router_bias_rate": rate}))
        params = jax.eval_shape(
            lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
        opt = jax.eval_shape(tfm.init_opt_state, params)
        text = tfm.make_train_step(cfg, lr=1e-4).lower(
            params, opt, i32(B, T), i32(B, T)).as_text()
    return text, params


def _cell_digest(config, traffic):
    text, params = _cell_step(config, traffic)
    text = re.sub(r"@(\w+?)_\d+\b", r"@\1", text)
    tree = str(jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)),
                                      params))
    return ((hashlib.sha256(text.encode()).hexdigest()[:16],
             text.count("\n")),
            hashlib.sha256(tree.encode()).hexdigest()[:16])


@pytest.mark.parametrize("cell", sorted(PARENT), ids=".".join)
def test_the_cells_tree_and_lowered_program_are_the_parents(cell):
    """No option an existing configuration has to set: the parameter tree
    and the whole lowered train step (loss, gradients, AdamW, the bias's
    rule) of each cell are, to the character, what the commit named above
    lowers."""
    assert _cell_digest(*cell) == PARENT[cell]


def test_berts_step_holds_one_erf_a_gelu_and_no_erfc():
    """The exact GELU engages as ONE `erf` wherever the step evaluates it:
    the trunk's layer, the layer again under `remat`, the MLM head's
    transform. `erfc`, which has no HLO opcode and which the compiler
    expands to some seventy vector operations an element, is gone (PR 62).
    Each of the three ends in a barrier over the pair (u, GELU') in the
    compute dtype, the fourth barrier being `remat`'s own over the layer's
    fourteen arguments: the backward pass reads the pair and derives no
    `erf` of its own (PR 65; the forward pass drops its GELU' unread, the
    head's is its saved residual; the compiled step's count on the chip is
    in PERF.md, section 5)."""
    text, _ = _cell_step("bert-base", "pretrain-seq512")
    assert len(re.findall(r"chlo\.erfc\b", text)) == 0
    assert len(re.findall(r"chlo\.erf\b", text)) == 3
    assert all("xf32>" in line for line in text.splitlines()
               if "chlo.erf" in line)
    barriers = [line.rstrip() for line in text.splitlines()
                if "stablehlo.optimization_barrier" in line]
    assert len(barriers) == 4
    pair = lambda shape: f": tensor<{shape}xbf16>, tensor<{shape}xbf16>"
    assert sum(b.endswith(pair("128x512x3072")) for b in barriers) == 2
    assert sum(b.endswith(pair("128x80x768")) for b in barriers) == 1
