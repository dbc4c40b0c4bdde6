"""A share's row loops (ISSUE 38; cut from test_lfm2_model.py, ISSUE 42:
they need no trained system): dispatch, activation and combine of `_moe_mlp`
loop over row chunks to a run-time bound, on the rows the share holds."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.kernels import grouped_matmul as gmm, registry
from hetu_tpu.models import hf_lfm2, transformer as tfm
from model_harness import seeded_params, seeded_tokens
import test_lfm2_model
from test_lfm2_model import HF, SHARE, reference

ROWS = 16           # a chunk of the loops below: 64 tokens x 2 picks = 128 rows


@pytest.fixture
def small_chunks(monkeypatch):
    """Chunks of ROWS rows at this file's width: the size follows from the
    shapes, so the test moves the bytes a chunk holds, as no caller can."""
    monkeypatch.setattr(tfm, "_ROW_CHUNK_BYTES", ROWS * 64 * 4)
    assert tfm._row_chunk(64, 64, jnp.float32) == ROWS
    # what the cell's shapes give: 1,024 rows of 4 KB, a divisor of S
    assert tfm._row_chunk(32768, 2048, jnp.bfloat16) <= 32768


def _layer_holding(n, seed=11):
    """One share layer (experts 2 and 3 of 8, top 2) and 64 token rows of
    which exactly `n` picks land on the held experts: a token's first
    feature drives both held experts' logits (+: both picked, -: neither),
    its second pulls them apart (one picked)."""
    cfg = hf_lfm2.config_from_hf(SHARE)
    p = jax.tree.map(lambda x: x[0], tfm.run_blocks(
        cfg, seeded_params(cfg, seed, bias=0.0)["blocks"])[1])
    router = 0.3 * jax.random.normal(jax.random.PRNGKey(seed), (64, 8))
    router = router.at[0].set(0.0).at[1].set(0.0)
    router = router.at[0, 2:4].set(8.0).at[1, 2].set(8.0).at[1, 3].set(-8.0)
    m = 0.1 * jax.random.normal(jax.random.PRNGKey(seed + 1), (64, 64))
    both, one = n // 2, n % 2
    flag = jnp.where(jnp.arange(64) < both, 0.5, -0.5)
    flag = flag.at[both].set(0.0) if one else flag
    # held tokens spread over the 64, not in front
    spread = jax.random.permutation(jax.random.PRNGKey(seed + 2), 64)
    m = m.at[:, 0].set(flag).at[:, 1].set(
        jnp.where((jnp.arange(64) == both) & bool(one), 0.5, 0.0))[spread]
    return cfg, {**p, "router": router}, m.reshape(2, 32, 64)


def _reference_share(m, p, first=2, n=2):
    """The held experts' part of the layer on m, by the cell's reference;
    biased GELU experts (no `w3`) by the same sum written here."""
    w = {"feed_forward.gate.weight": p["router"].T,
         "feed_forward.expert_bias": p[tfm.ROUTER_BIAS]}
    if "w3" not in p:
        top_w, top_e = reference._picks(m.reshape(-1, 64), w, HF)
        weight = jnp.sum(jnp.where(
            top_e[None] == first + jnp.arange(n)[:, None, None],
            top_w[None], 0.0), -1)                               # (E, S)
        u = jax.nn.gelu(jnp.einsum("sd,edf->esf", m.reshape(-1, 64), p["w1"])
                        + p["b1"][:, None])
        ys = jnp.einsum("esf,efd->esd", u, p["w2"]) + p["b2"][:, None]
        return jnp.einsum("es,esd->sd", weight, ys), (top_w, top_e)
    for e in range(n):
        for name in ("w1", "w3", "w2"):
            w[f"feed_forward.experts.{first + e}.{name}.weight"] = \
                p[name][e].T
    return reference._experts_math(
        m.reshape(-1, 64), w, {**HF, "num_experts": n}, first)


@pytest.fixture
def nan_where_nothing_was_written(monkeypatch):
    """`lax.empty` filled with NaN: a row that no loop wrote holds what
    poisons any sum that reads it (on the host an empty buffer is zeros, on
    the chip whatever was there)."""
    monkeypatch.setattr(jax.lax, "empty", lambda shape, dtype: jnp.full(
        shape, jnp.nan, dtype))


HELD_CASES = [0, 1, ROWS - 1, ROWS, ROWS + 1, 32, 128]


@pytest.mark.parametrize("case,mlp", [
    (n, mlp) for mlp in ("swiglu", "gelu") for n in HELD_CASES]
    + [("shares", "swiglu")])
def test_a_share_works_on_the_rows_it_holds(
        case, mlp, small_chunks, nan_where_nothing_was_written):
    """Forward and `jax.grad` of a share layer against the reference with
    0, 1, a chunk less one, a chunk, a chunk and one, a quarter and ALL of
    the S*k pick rows held, whatever the rows no loop wrote hold; and the
    four shares still add up. Biased GELU experts too: their biases'
    gradient sums over ALL rows."""
    if case == "shares":
        return (test_lfm2_model.
                test_the_four_shares_of_an_expert_layer_add_up_to_the_whole())
    cfg, p, m = _layer_holding(case)
    leaves = ("w1", "w3", "w2", "router")
    if mlp == "gelu":
        cfg = dataclasses.replace(cfg, mlp="gelu")
        p = {n: x for n, x in p.items() if n != "w3"}
        for i, (b, width) in enumerate((("b1", 48), ("b2", 64))):
            p[b] = 0.1 * jax.random.normal(jax.random.PRNGKey(7 + i),
                                           (2, width))
        leaves = ("w1", "b1", "w2", "b2", "router")
    cot = jax.random.normal(jax.random.PRNGKey(5), (64, 64))

    def mine(m, part):
        out, _ = tfm._moe_mlp(m, {**p, **part}, cfg, None)
        return jnp.sum(out.reshape(-1, 64) * cot), out

    def theirs(m, part):
        out, picks = _reference_share(m, {**p, **part})
        return jnp.sum(out * cot), (out, picks)

    with jax.default_matmul_precision("highest"):
        part = {n: p[n] for n in leaves}
        (_, out), got = jax.value_and_grad(mine, (0, 1), has_aux=True)(
            m, part)
        (_, (want_out, (_, top_e))), want = jax.value_and_grad(
            theirs, (0, 1), has_aux=True)(m, part)
    assert int(np.sum((np.asarray(top_e) >= 2) & (np.asarray(top_e) < 4))
               ) == case
    np.testing.assert_allclose(np.asarray(out.reshape(-1, 64)),
                               np.asarray(want_out), rtol=1e-4, atol=1e-7)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        scale = max(float(np.abs(np.asarray(b)).max()), 1e-6)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=2e-5 * scale)
    if case == 0:
        assert float(jnp.abs(out).max()) == 0.0
    # the rows the loops cover, by the helper they take their trips from
    top_p, top_e, counts, _, _ = tfm._route(m.reshape(-1, 64), p, cfg)
    held = (top_e >= 2) & (top_e < 4)
    order = jnp.argsort(jnp.where(held.reshape(-1), top_e.reshape(-1) - 2, 2),
                        stable=True).astype(jnp.int32)
    plan = tfm._share_plan(order, jnp.argsort(order).astype(jnp.int32), held,
                           counts[2:4], ROWS)
    assert int(plan["rows_run"]) == min(-(-case // ROWS) * ROWS, 128)
    assert int(plan["tokens_run"]) == -(-((case + 1) // 2) // ROWS) * ROWS


@pytest.fixture
def grouped_matmul_kernels_taken(monkeypatch):
    """The experts' grouped matmuls by the Pallas kernels, interpreted: what
    `registry.dispatch` serves on a TPU in one program at any width."""
    monkeypatch.setattr(registry, "_on_tpu", lambda: True)
    registry.reset_stats()
    yield
    assert set(registry.dispatch_stats()) == {(gmm.GROUPED_MATMUL, "pallas")}


@pytest.mark.parametrize("case,mlp", [
    (n, mlp) for mlp in ("swiglu", "gelu") for n in (0, 1, ROWS + 1, 128)])
def test_a_share_works_on_its_rows_by_the_grouped_matmul_kernels(
        case, mlp, small_chunks, nan_where_nothing_was_written,
        grouped_matmul_kernels_taken):
    """The same layer and the same reference with every grouped matmul,
    forward, dx and dW, in the kernels of `kernels/grouped_matmul.py`: no
    row held, one, a chunk and one, all; NaN where no loop wrote."""
    test_a_share_works_on_the_rows_it_holds(case, mlp, None, None)


def test_the_share_step_reads_nothing_on_the_host_and_counts_its_rows(
        small_chunks):
    """The bound is a number on the device: the compiled step holds loops
    and no callback, outfeed or host transfer; `rows_run` is `held` up to
    whole chunks, clipped to S*k."""
    cfg = hf_lfm2.config_from_hf(SHARE, router_bias_rate=1e-3)
    params = seeded_params(cfg)
    tokens, targets = seeded_tokens(SHARE, 8)
    text = tfm.make_train_step(cfg).lower(
        params, tfm.init_opt_state(params), tokens,
        targets).compile().as_text()
    for word in ("callback", "outfeed", "infeed", "SendToHost", "send(",
                 "recv("):
        assert word not in text, word
    loops = [l for l in text.splitlines() if " while(" in l]
    assert len(loops) > 3       # the three runs' scans and the rows' loops
    skew = jnp.zeros((8,)).at[2:4].set(5.0)
    shift = lambda by: jax.tree_util.tree_map_with_path(
        lambda path, x: x + by if tfm._is_router_bias(path) else x, params)
    for p, held in ((params, None), (shift(skew), 128), (shift(-skew), 0)):
        stats = tfm.moe_routing_stats(p, tokens, cfg)
        n = np.asarray(stats["held"])
        assert held is None or n.tolist() == [held] * 4
        np.testing.assert_array_equal(
            np.asarray(stats["rows_run"]),
            np.minimum(-(-n // ROWS) * ROWS, 128))
    # not a share: every row, whatever the routing
    whole = hf_lfm2.config_from_hf(HF)
    stats = tfm.moe_routing_stats(seeded_params(whole), tokens, whole)
    assert np.asarray(stats["rows_run"]).tolist() == [128] * 4
