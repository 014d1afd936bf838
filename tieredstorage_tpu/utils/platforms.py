"""Platform set-up shared by tests, the sidecar, chip_smoke.py and benchmark/run.py:
virtual-CPU-mesh pinning, the persistent compile cache, and the count of
programs JAX traces.

Multi-chip sharding paths are validated on a virtual CPU mesh
(``--xla_force_host_platform_device_count``). The flag is read once, when
the CPU backend starts, so every caller that wants the virtual mesh must
force the platform explicitly *before* the first JAX backend initialization.
"""

from __future__ import annotations

import os
import pathlib
import re
import threading

from tieredstorage_tpu.utils.locks import new_lock

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
    watch_program_traces()
    return path


# --- programs traced ---
#
# The persistent cache saves a program's backend compile, not its trace and
# lowering, and on the chip those are seconds per window shape (PERF.md): a
# retrace that then hits the cache is invisible to a count of compiles. JAX
# reports both stages through `jax.monitoring` whether or not the cache hits.
# Trace events nest (a jit called under a jit reports its own, inside the
# outer one's duration) and the outermost is the last before its program's
# lowering event on the same thread, so a program is counted at its lowering,
# with that one trace's seconds.
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_TRACES = {"program_traces": 0, "program_trace_seconds": 0.0}
_TRACES_MU = new_lock("platforms._TRACES_MU")
_TRACES_TLS = threading.local()
_watching = False


def _on_compile_stage(event: str, seconds: float, **_kwargs) -> None:
    if event == _TRACE_EVENT:
        _TRACES_TLS.last_trace_s = seconds
    elif event == _LOWER_EVENT:
        seconds += getattr(_TRACES_TLS, "last_trace_s", 0.0)
        _TRACES_TLS.last_trace_s = 0.0
        _TRACES_TLS.count = getattr(_TRACES_TLS, "count", 0) + 1
        with _TRACES_MU:
            _TRACES["program_traces"] += 1
            _TRACES["program_trace_seconds"] += seconds


def watch_program_traces() -> None:
    """Count every program this process traces and lowers from here on
    (idempotent; `enable_compile_cache()` calls it)."""
    global _watching
    import jax.monitoring

    with _TRACES_MU:
        if _watching:
            return
        _watching = True
    jax.monitoring.register_event_duration_secs_listener(_on_compile_stage)


def program_trace_stats() -> dict:
    """`program_traces` (programs traced and lowered since
    `watch_program_traces()`) and `program_trace_seconds` (their outermost
    trace plus their lowering); noughts in a process that never watched."""
    with _TRACES_MU:
        return dict(_TRACES)


def thread_program_traces() -> int:
    """Programs the CALLING thread traced and lowered: the delta around a
    jitted call says whether that call traced."""
    return getattr(_TRACES_TLS, "count", 0)
