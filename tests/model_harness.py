"""What the `test_<model>_model.py` files share: a model file states its HF
keys, its wrong-on-purpose table and its mechanism tests, and takes from here
the reference by its cell's name, seeded tokens and weights, the relative
error, the loader's round trip, a refusal by name, and model-sized
computations compiled ONCE a config (`jitted`). Nothing here says what a
model IS."""
import functools
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.kernels import rope as rope_kernel
from hetu_tpu.models import transformer as tfm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@functools.lru_cache(maxsize=None)
def load_reference(config_name):
    """`benchmark/configs/<config_name>/reference.py`, imported by its path
    (the directory's name is no identifier)."""
    path = os.path.join(ROOT, "benchmark", "configs", config_name,
                        "reference.py")
    name = re.sub(r"\W", "_", config_name) + "_reference"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def rel(got, want):
    """The root mean square of the difference over the reference's own."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / max(np.sqrt(np.mean(want ** 2)), 1e-30))


def seeded_tokens(hf, seed, B=2, T=32):
    """-> (tokens, next-token targets), (B, T) ids under `hf["vocab_size"]`."""
    ids = jax.random.randint(jax.random.PRNGKey(seed), (B, T + 1), 0,
                             hf["vocab_size"])
    return ids[:, :-1], ids[:, 1:]


def seeded_params(cfg, seed=0, bias=0.05, noisy=(), tenfold=()):
    """The program's own initial weights from `seed`, with what it makes
    constant moved so that it matters: the routers' selection bias normal of
    std `bias` (None: left as it is), the leaves named in `noisy` (names, or
    a predicate of the name) plus 0.1 normal, those named in `tenfold` times
    ten."""
    params = tfm.init_params(jax.random.PRNGKey(seed), cfg)
    key = jax.random.PRNGKey(seed + 100)
    is_noisy = noisy if callable(noisy) else (lambda name: name in noisy)

    def off(path, x):
        if bias is not None and tfm._is_router_bias(path):
            return bias * jax.random.normal(key, x.shape)
        if is_noisy(path[-1].key):
            return x + 0.1 * jax.random.normal(key, x.shape)
        if path[-1].key in tenfold:
            return 10.0 * x
        return x

    return jax.tree_util.tree_map_with_path(off, params)


def round_trip(loader, params, cfg, back=None):
    """`state_dict_from_params` and back (`back(sd, cfg)`; by default the
    loader's `params_from_state_dict` on jax arrays) is the identity: the
    same tree, every leaf to the bit. -> the state dict, for the file's own
    assertions about HF names and shapes."""
    sd = loader.state_dict_from_params(params, cfg)
    again = (back or functools.partial(loader.params_from_state_dict,
                                       xp=jnp))(sd, cfg)
    assert jax.tree.structure(again) == jax.tree.structure(params)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(params),
                            jax.tree.leaves(again)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(path))
    return sd


def refuses(fn, named, error=AssertionError):
    """`fn()` raises `error` with `named`, taken literally, in its text."""
    with pytest.raises(error, match=re.escape(named)):
        fn()


def hidden_after_runs(params, tokens, cfg):
    """The residual stream after each run of `layer_runs(cfg)`."""
    h, after = tfm.embed_tokens(params, tokens, cfg), []
    for (kind, _), blocks in zip(tfm.layer_runs(cfg),
                                 tfm.run_blocks(cfg, params["blocks"])):
        h = tfm._through_run(h, blocks, cfg, kind)
        after.append(h)
    return after


loss_and_grads = jax.value_and_grad(tfm.loss_fn)
grads_of_loss = jax.grad(tfm.loss_fn)


@functools.lru_cache(maxsize=None)
def jitted(fn, cfg, *variant):
    """-> `jax.jit` of `fn(*arrays, cfg)`, built once a (fn, cfg, variant):
    a computation of a model's size compiles as ONE program and not a
    primitive at a time, and the cases of a file that share a config share
    it. `fn` is a module-level function (`tfm.loss_fn`, `grads_of_loss`,
    `hidden_after_runs`), or the key misses. A case that monkeypatches what
    `fn` traces names itself in `variant`: the patched program is its own."""
    return jax.jit(lambda *arrays: fn(*arrays, cfg))


@pytest.fixture()
def rope_kernel_taken(monkeypatch):
    """What `transformer._rope_q` does on a TPU: the kernel wherever its
    blocks divide the shape. -> the list of the shapes it was called at."""
    seen = []
    rotate = rope_kernel._rotate

    def noting(x, *rest):
        seen.append(x.shape)
        return rotate(x, *rest)

    monkeypatch.setattr(rope_kernel, "_on_tpu", lambda: True)
    monkeypatch.setattr(rope_kernel, "_rotate", noting)
    return seen


def sub_jaxprs(eqn):
    """The jaxprs an equation carries in its parameters."""
    for v in eqn.params.values():
        for x in (v if isinstance(v, (list, tuple)) else [v]):
            if hasattr(x, "jaxpr") and hasattr(x, "consts"):
                yield x.jaxpr
            elif hasattr(x, "eqns"):
                yield x
