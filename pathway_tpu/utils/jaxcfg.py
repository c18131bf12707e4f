"""The one door to JAX configuration.

Every module of the package that imports ``jax`` imports this module first.
Importing it only sets configuration values — it never initialises a
backend, so it cannot take the chip in a process that should not hold it.

The persistent compilation cache is placed by :func:`place_compile_cache`
(run at import).
"""

from __future__ import annotations

import os

import jax

#: ``<checkout>/.jax_cache`` (git-ignored). The directory is part of the
#: cache key, so it is one fixed path: a cache that moves never hits.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def place_compile_cache() -> None:
    """Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and no
    directory is set here; otherwise the cache lives in the checkout.

    JAX's default entry threshold (programs that took >= 1.0 s to compile)
    would skip most of what this system compiles — one small program per
    embedder shape bucket and per eager index update — and on a fresh chip
    host those are what a warm start saves. Every program is cached unless
    the caller chose a threshold through the environment."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def guard_cpu_platform(force_device_count: int) -> None:
    """For CPU test runs (``JAX_PLATFORMS=cpu``): pin ``jax_platforms`` and
    force a virtual device count. Must run before a backend initialises."""
    if os.environ.get("JAX_PLATFORMS", "") != "cpu":
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={force_device_count}"
        ).strip()
    jax.config.update("jax_platforms", "cpu")


place_compile_cache()
