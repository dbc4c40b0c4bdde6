"""Contracts of the chip bring-up (ISSUE 21): no fallback hides the device,
one placeable compile cache, a PS library keyed on its sources, one
process per chip. All CPU, seconds each."""
import os
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu import utils

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- tpu(i): strict off the CPU pin ------------------------------------------

def _fake_tpus(n):
    return [types.SimpleNamespace(platform="tpu", id=i) for i in range(n)]


def test_tpu_ctx_raises_without_cpu_pin_and_without_a_tpu(monkeypatch):
    monkeypatch.setattr(utils, "cpu_pinned", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    with pytest.raises(RuntimeError, match="backend is 'cpu'"):
        ht.tpu(0).jax_device()


def test_tpu_ctx_out_of_range_raises_instead_of_wrapping(monkeypatch):
    monkeypatch.setattr(utils, "cpu_pinned", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    devs = _fake_tpus(2)
    monkeypatch.setattr(jax, "devices", lambda *a: devs)
    assert ht.tpu(1).jax_device() is devs[1]
    with pytest.raises(RuntimeError, match="only 2 tpu device"):
        ht.tpu(2).jax_device()
    # under the pin too: the virtual CPU devices are counted the same way
    monkeypatch.undo()
    with pytest.raises(RuntimeError, match="cpu device"):
        ht.tpu(len(jax.devices("cpu"))).jax_device()


def test_ensure_devices_never_clears_a_non_cpu_backend(monkeypatch):
    import jax.extend.backend as jax_backend
    monkeypatch.setattr(utils, "cpu_pinned", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: _fake_tpus(4))

    def boom():
        raise AssertionError("cleared a backend that holds a chip")
    monkeypatch.setattr(jax_backend, "clear_backends", boom)
    utils.ensure_devices(4)                       # enough chips: fine
    with pytest.raises(RuntimeError, match="need 8 devices"):
        utils.ensure_devices(8)                   # too few: raise, no CPU


# -- the compile cache ---------------------------------------------------------

def test_compile_cache_honours_env_and_is_otherwise_fixed(monkeypatch,
                                                          tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert utils.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # untouched
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = os.path.join(ROOT, ".jax_cache")
    assert utils.compile_cache_path() == fixed
    assert utils.compile_cache_path() == fixed    # twice running: no pid,
    #                                               time or temp name
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        assert utils.use_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          floor)


# -- csrc/build.py: keyed on content, not mtime ----------------------------------

def test_native_build_rebuilds_on_header_content_not_mtime(monkeypatch,
                                                           tmp_path):
    from hetu_tpu.csrc import build as b
    (tmp_path / "lib.cc").write_text(
        '#include "v.h"\nextern "C" int version() { return V; }\n')
    hdr = tmp_path / "v.h"
    hdr.write_text("#define V 1\n")
    st = os.stat(hdr)
    monkeypatch.setattr(b, "_CSRC", str(tmp_path))
    monkeypatch.setattr(b, "_BUILD", str(tmp_path / "build"))
    monkeypatch.setattr(b, "_TARGETS", {
        "libv.so": {"srcs": ["lib.cc"], "deps": ["v.h"]}})
    first = b.build("libv.so")
    assert b.build("libv.so") == first            # same sources: reused
    hdr.write_text("#define V 2\n")
    os.utime(hdr, ns=(st.st_atime_ns, st.st_mtime_ns))   # mtime unchanged
    # a stale library copied along with a checkout, newer than everything
    stale = tmp_path / "build" / "libv.so"
    stale.write_bytes(b"not a library")
    second = b.build("libv.so")
    assert second != first and not os.path.exists(first)
    assert not stale.exists()                     # never loaded, swept
    import ctypes
    assert ctypes.CDLL(second).version() == 2


# -- fused optimizer kernels: gridded past the VMEM tile ----------------------------

def test_fused_opt_runs_gridded_past_the_vmem_tile():
    from hetu_tpu.kernels import fused_opt
    # 2500 lane rows: two full 1024-row blocks and a partial third
    shape = (2500, 128)
    assert shape[0] > fused_opt._BLOCK_ROWS
    rng = np.random.RandomState(0)
    p, g, m = (jnp.asarray(rng.randn(*shape), jnp.float32) for _ in range(3))
    v = jnp.abs(jnp.asarray(rng.randn(*shape), jnp.float32))
    kw = dict(beta1=0.9, beta2=0.999, eps=1e-7, weight_decay=0.01)
    got = jax.jit(lambda *a: fused_opt._adam_pallas(*a, **kw))(
        p, g, m, v, 3.0, 0.01)
    want = jax.jit(lambda *a: fused_opt._adam_xla(*a, **kw))(
        p, g, m, v, 3.0, 0.01)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=0)
    got = jax.jit(lambda p, g: fused_opt._sgd_pallas(p, g, 0.1, l2reg=1e-4))(
        p, g)
    want = jax.jit(lambda p, g: fused_opt._sgd_xla(p, g, 0.1, l2reg=1e-4))(
        p, g)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6, rtol=0)


def test_auto_fallback_records_its_reason():
    from hetu_tpu.kernels import registry
    registry.reset_stats()
    try:
        with registry.active("auto"):
            registry.dispatch("fused_sgd", jnp.ones((8, 128)),
                              jnp.ones((8, 128)), 0.1, l2reg=0.0)
            registry.dispatch("fused_sgd", jnp.ones((8, 128), jnp.bfloat16),
                              jnp.ones((8, 128), jnp.bfloat16), 0.1,
                              l2reg=0.0)
        why = registry.fallback_reasons()
        assert why[("fused_sgd", "backend is not a tpu")] == 1
        assert any("f32" in r for (_k, r) in why)   # the dtype's doing
    finally:
        registry.reset_stats()


def test_named_axis_probe_sees_shard_map_and_plain_jit():
    from jax.sharding import Mesh, PartitionSpec as P
    from hetu_tpu.kernels import registry
    seen = {}

    def inside(x):
        seen["shard_map"] = registry._in_named_axis_trace()
        return x
    mesh = Mesh(np.array(jax.devices()[:2]), ("a",))
    jax.jit(jax.shard_map(inside, mesh=mesh, in_specs=P("a"),
                          out_specs=P("a")))(jnp.ones((2, 2)))

    def plain(x):
        seen["jit"] = registry._in_named_axis_trace()
        return x
    jax.jit(plain)(1.0)
    assert seen == {"shard_map": True, "jit": False}


# -- peaks: one table, no default ------------------------------------------------

def test_unknown_device_kind_yields_no_mfu():
    from hetu_tpu.telemetry import profiler as prof
    assert prof.device_peaks("TPU v5 lite")["tflops"] == 197.0
    assert "source" in prof.device_peaks("TPU v5 lite")
    assert prof.device_peaks("unknown") is None
    assert prof.device_peaks("cpu") is None
    assert prof.mfu(1e12, 0.01, "cpu") is None
    assert prof.mfu(1e12, 0.01, "TPU v5 lite") == pytest.approx(
        1e12 / 0.01 / 197e12)
    from hetu_tpu.telemetry import hetutop
    assert hetutop._mfu_pair({"hetu_flops_per_step_6nd": 1e12}, {}, 10.0,
                             None) == (None, None)


# -- one process per chip ----------------------------------------------------------

def test_runner_gives_each_local_worker_its_own_chip(monkeypatch):
    from hetu_tpu import runner
    monkeypatch.setattr(runner, "local_tpu_chips", lambda: 4)
    # pinned to the CPU on purpose, or a single worker: nothing to assign
    assert runner.plan_local_chips(4, {"JAX_PLATFORMS": "cpu"}) is None
    assert runner.plan_local_chips(1, {}) is None
    assert runner.worker_chip_env(None, 0) == {}
    chips = runner.plan_local_chips(4, {})
    envs = [runner.worker_chip_env(chips, w) for w in range(4)]
    assert len({e["TPU_VISIBLE_CHIPS"] for e in envs}) == 4
    assert all(e["TPU_PROCESS_BOUNDS"] == "1,1,1" for e in envs)
    with pytest.raises(SystemExit, match="5 local workers but 4 TPU chip"):
        runner.plan_local_chips(5, {})
    with pytest.raises(RuntimeError, match="no chip"):
        runner.worker_chip_env(chips, 4)          # an elastic grow too far
