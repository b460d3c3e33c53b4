"""Test harness config: force JAX onto a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; per the build contract the
sharded paths are validated on a virtual CPU mesh
(`--xla_force_host_platform_device_count=8`).

The tests never touch an accelerator: `JAX_PLATFORMS=cpu` is set here
and again through `jax.config.update`, which still takes effect where
something imported jax before conftest ran, because no backend has been
initialized yet.  The program is proved on the chip by `chip_smoke.py`,
and `tests/test_chip_compile.py` asks the chip's compiler without one.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent XLA compile cache (same one bench.py/__graft_entry__ use):
# the suite is dominated by CPU XLA compiles; caching them on disk makes
# re-runs start warm.
from libjitsi_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()
