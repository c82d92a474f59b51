"""JAX backend environment control shared by tests, bench, and driver entry.

Two knobs, both applied before the first backend init: where the
persistent XLA compile cache lives, and (for what is CPU-only by design:
tests, the multichip dryrun, the bench host children) the forced CPU
backend with N emulated devices.
"""

from __future__ import annotations

import os
import re

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def compile_cache_dir() -> str:
    """Where compiled programs are kept: ``JAX_COMPILATION_CACHE_DIR``
    when the environment sets it, else the fixed ``<checkout>/.jax_cache``
    (the path is part of the cache key, so it never carries a temp name,
    pid or time).  The AOT bundle's default directory sits under it."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(_REPO, ".jax_cache")


def enable_compile_cache(min_compile_secs: float = 2.0) -> None:
    """Turn on jax's persistent on-disk XLA compile cache.

    The ed25519 kernel takes ~1 min to compile per batch-shape bucket;
    the cache makes every repeat process start in milliseconds.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set jax reads it itself and no
    directory is set in code."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_secs)


def force_cpu_backend(min_devices: int | None = None) -> None:
    """Force jax onto the CPU backend, optionally with >= min_devices
    emulated devices.  Must run before any backend init (the first
    ``jax.devices()``/jit): jax refuses the device-count update after
    that, and the platform update would silently not apply."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    if min_devices is not None:
        # an XLA_FLAGS count that already suffices stands (the bench's
        # mesh children size their own emulation that way)
        m = re.search(r"xla_force_host_platform_device_count=(\d+)",
                      os.environ.get("XLA_FLAGS", ""))
        if m is None or int(m.group(1)) < min_devices:
            jax.config.update("jax_num_cpu_devices", min_devices)
