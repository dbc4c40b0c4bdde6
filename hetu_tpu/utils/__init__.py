"""Shared utilities for examples, tests and the driver entry points."""
from __future__ import annotations

import importlib
import os
import sys

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cpu_pinned() -> bool:
    """Was this process pinned to the CPU backend on purpose
    (``JAX_PLATFORMS=cpu`` / ``jax_platforms == "cpu"``, which the test
    suite does)? Every place that may resolve a ``tpu`` request onto CPU
    devices asks this first: without the pin a missing TPU is an error,
    never a quiet CPU run."""
    return (jax.config.jax_platforms or "").strip().lower() == "cpu"


def import_example_models(suite):
    """Import ``examples/<suite>/models`` under the bare name ``models``,
    as the example trainers do. The cnn and ctr suites both use that name,
    so another suite's cached package is dropped first."""
    path = os.path.join(_CHECKOUT, "examples", suite)
    target = os.path.join(path, "models")
    current = sys.modules.get("models")
    if current is not None and \
            os.path.normpath(os.path.dirname(current.__file__)) != target:
        for k in [k for k in sys.modules
                  if k == "models" or k.startswith("models.")]:
            sys.modules.pop(k)
    if path in sys.path:
        sys.path.remove(path)
    sys.path.insert(0, path)
    return importlib.import_module("models")


def compile_cache_path() -> str:
    """Directory of the persistent compilation cache: wherever
    ``JAX_COMPILATION_CACHE_DIR`` points, else the fixed
    ``<checkout>/.jax_cache`` (git-ignored). The path is part of the
    cache key, so it never carries a temporary name, a pid or a time.
    Pure — launcher parents hand it to workers through their
    environment without touching the jax config."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_CHECKOUT, ".jax_cache"))


def use_compile_cache() -> str:
    """Point this process at the persistent compilation cache and return
    its directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set nothing is
    touched — JAX reads the variable itself. Otherwise the fixed
    in-checkout directory is configured, with the compile-time floor at
    zero so a second run of the same program compiles nothing."""
    path = compile_cache_path()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def ensure_devices(n_devices: int) -> None:
    """Ensure >= n_devices jax devices exist. Under the CPU pin a virtual
    CPU mesh of that size is provisioned (the reference requires a
    physical GPU per rank; multi-chip layouts are validated on virtual
    devices, SURVEY.md §4's local-process-cluster strategy). On any other
    backend too few devices is an error: a process that holds a chip is
    never torn down and moved to the CPU behind the caller's back.
    """
    if not cpu_pinned():
        have = len(jax.devices())
        if have < n_devices:
            raise RuntimeError(
                f"need {n_devices} devices, the {jax.default_backend()} "
                f"backend has {have}; set JAX_PLATFORMS=cpu to validate "
                "the layout on a virtual CPU mesh instead")
        return
    if len(jax.devices()) >= n_devices:
        return
    # the pinned CPU backend is live with too few devices:
    # jax_num_cpu_devices refuses updates until it is cleared
    import jax.extend.backend as jax_backend
    jax_backend.clear_backends()
    jax.config.update("jax_num_cpu_devices", n_devices)
    if len(jax.devices()) < n_devices:
        raise RuntimeError(
            f"virtual CPU mesh provisioning failed: need {n_devices}, "
            f"got {len(jax.devices())}")
