"""The one door to JAX configuration.

Every module of the package that imports ``jax`` imports this module first.
Importing it only sets configuration values — it never initialises a
backend, so it cannot take the chip in a process that should not hold it.

- The persistent compilation cache is placed by :func:`place_compile_cache`
  (run at import).
- x64 is a property of the *host* expression tier: the XLA expression
  kernels need it so INT/FLOAT columns keep python int64/float64 semantics,
  and f64 is not native on the TPU. :func:`enable_x64_on_cpu` turns it on
  only in a CPU-only process and is called where those kernels are built,
  not at import.
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


_platform: str | None = None


def platform() -> str:
    """The platform JAX programs of this process run on. With
    ``JAX_PLATFORMS`` set the environment decides; otherwise this asks the
    default backend, which initialises it — call it only from a process
    that is about to run a JAX program anyway."""
    global _platform
    if _platform is None:
        env = os.environ.get("JAX_PLATFORMS", "")
        _platform = env.split(",")[0] if env else jax.default_backend()
    return _platform


def enable_x64_on_cpu() -> bool:
    """Enable x64 when this process computes on the CPU only; returns
    whether x64 is on. On an accelerator it stays off and the host
    expression tier runs its numpy kernels."""
    if platform() == "cpu" and not jax.config.jax_enable_x64:
        jax.config.update("jax_enable_x64", True)
    return bool(jax.config.jax_enable_x64)


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
