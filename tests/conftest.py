"""Test configuration: force a virtual 8-device CPU platform.

This is the standard way to exercise pjit/shard_map sharding without a TPU pod
(SURVEY §4): tests that need a mesh get 8 host devices; everything else just
runs on CPU for speed and determinism.

The XLA flag must be set BEFORE jax import. The platform is pinned through
``jax.config`` as well as ``JAX_PLATFORMS``: a config value set earlier in the
process wins over the environment variable.

Compile-heavy tests dominate the suite's wall-clock; a persistent XLA
compilation cache makes every run after the first fast; it lives where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``<checkout>/.jax_cache``
(``cli.common.enable_compile_cache``). Tests relying on
tight cross-run numerics opt into matmul precision locally via
``jax.default_matmul_precision("highest")`` instead of a global override
(which made every compile slower).
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
from videop2p_tpu.cli.common import enable_compile_cache  # noqa: E402

enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
