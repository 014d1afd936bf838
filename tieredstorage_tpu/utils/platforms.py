"""Platform set-up shared by tests, the sidecar, chip_smoke.py and bench.py:
virtual-CPU-mesh pinning and the persistent compile cache.

Multi-chip sharding paths are validated on a virtual CPU mesh
(``--xla_force_host_platform_device_count``). The flag is read once, when
the CPU backend starts, so every caller that wants the virtual mesh must
force the platform explicitly *before* the first JAX backend initialization.
"""

from __future__ import annotations

import os
import pathlib
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"
_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def pin_virtual_cpu(min_devices: int = 8) -> None:
    """Pin JAX to the host platform with at least ``min_devices`` virtual CPU
    devices. Safe to call multiple times; raises if JAX initialized a backend
    with fewer devices before the flag could take effect."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    match = re.search(rf"{_COUNT_FLAG}=(\d+)", flags)
    count = max(1, min_devices)
    if match is None:
        if count > 1:
            os.environ["XLA_FLAGS"] = f"{flags} {_COUNT_FLAG}={count}".strip()
    elif int(match.group(1)) < count:
        os.environ["XLA_FLAGS"] = flags.replace(
            match.group(0), f"{_COUNT_FLAG}={count}"
        )

    import jax

    jax.config.update("jax_platforms", "cpu")
    cpus = jax.devices("cpu")
    if len(cpus) < min_devices:
        raise RuntimeError(
            f"virtual CPU mesh has {len(cpus)} devices, need {min_devices}; "
            f"a JAX backend was initialized before {_COUNT_FLAG} could be "
            "raised — call pin_virtual_cpu() before any jax.devices()/jit use"
        )


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.
    Call before the first trace (the cache is bound at the first compile).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, that directory is used as it
    is and no other is named in code. Otherwise the cache lives at
    ``<checkout>/.jax_cache`` — derived from this file's location, never
    from a temp dir, a pid or a clock: the path is part of the cache key, so
    a directory that moves never hits. JAX creates it on the first write."""
    import jax

    path = os.environ.get(_CACHE_ENV) or str(
        pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"
    )
    jax.config.update("jax_compilation_cache_dir", path)
    return path
