"""Shared test setup: CPU backend + persistent XLA compilation cache +
forced multi-device host platform for the sharded-serving tests.

The tier-1 suite is compile-bound (dozens of small jitted models), so a
persistent cache cuts repeat runs roughly in half.  Cache misses (first run,
jax upgrade) only cost the compiles the run would have done anyway.  The
cache location follows ``repro.launch.compile_cache`` (the environment's
``JAX_COMPILATION_CACHE_DIR``, else the checkout's ``.jax_cache/``).

The 8-device host platform (set BEFORE jax initializes) backs the
tests/test_distributed.py and tests/test_mesh_properties.py mesh fixtures:
the sharded decode path must run on real (if fake) multi-device meshes
in-process, including the 2×4 (data × model) placement (DESIGN.md §8).
Single-device tests are unaffected — without sharding annotations jax
places everything on device 0.  An explicitly provided XLA_FLAGS wins (the
subprocess dry-run tests set their own device count).
"""
import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402  (the flag must precede jax's backend init)

from repro.launch.compile_cache import use_compile_cache  # noqa: E402

jax.config.update("jax_platform_name", "cpu")
use_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)
