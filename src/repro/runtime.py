"""Process set-up shared by the entry points: the persistent compilation
cache and the visible-device check.

Importing this module touches no device; each function is called once,
by an entry point, before its first compile.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import List

#: the checkout's own cache: fixed, because the path is part of the key
#: under which a later process finds what an earlier one compiled
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its path.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here; otherwise the cache lives at
    ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)


def visible_devices(n: int) -> List:
    """The first ``n`` JAX devices; ``SystemExit`` when fewer are visible
    (programs are never silently stacked on one device)."""
    import jax
    devs = jax.devices()
    if len(devs) < n:
        raise SystemExit(
            f"{n} device(s) asked for but only {len(devs)} "
            f"{devs[0].platform} device(s) visible; on a CPU host set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n}")
    return devs[:n]
