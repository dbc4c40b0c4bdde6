"""The benchmark's tests run under the CPU pin on four virtual devices (the
dp=4 layout is rehearsed there); the pin is made on purpose, as in
tests/conftest.py."""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
