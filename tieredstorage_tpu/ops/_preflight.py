"""Shared first-use preflight for the Pallas kernel gates — a gate that
fails loud.

Each kernel (ops/aes_pallas.py, ops/ghash_pallas.py) is guarded by a
first-use preflight that compiles and runs it on a minimal tile and
cross-checks the result against an exact reference. The gates are read at
trace time, and the caller's jit cache pins whichever program the first
trace built, so the answer has to be right the first time:

- On a TPU backend a kernel that does not lower, does not run, or diverges
  from its reference **raises** `KernelPreflightError`. Nothing is memoized
  as "unavailable" and no XLA form takes the kernel's place: a program
  without its kernels is a different program (the whole-window XLA circuit
  takes minutes to compile per shape), and running it in silence would file
  its cost under the kernel's name. Only a pass is memoized.
- Off the TPU (the CPU backend of the test suite) the gates answer "no" and
  callers keep the XLA / interpret-mode forms. That is a platform fact, read
  once per process by `on_tpu`, not a fallback.
- If JAX cannot name its backend, that exception reaches the caller too.

The attempt runs on a thread of its own. A gate is consulted while the
caller's jit is tracing, where every jnp op would be staged into that trace
and the verdict's `bool()` would fail; JAX's trace state is thread-local, so
a fresh thread computes eagerly. `jax.ensure_compile_time_eval` is not a way
out: under it a Pallas kernel cannot be traced at all — the Python-int ref
indices in its body fold to concrete scalars, which `pallas_call` refuses as
captured constants. (That is what the first run on the chip raised, PR 21;
the gate this one replaces would have filed it as "kernel unavailable".)
"""

from __future__ import annotations

import functools
from concurrent.futures import ThreadPoolExecutor
from typing import Callable


class KernelPreflightError(RuntimeError):
    """A Pallas kernel failed its first-use preflight on a TPU backend."""


@functools.cache
def on_tpu() -> bool:
    """Whether the default JAX backend is a TPU — read once per process (a
    failing probe raises and is not cached)."""
    import jax

    return jax.default_backend() == "tpu"


def interpret_off_device() -> bool:
    """True when the backend is not a TPU, so a *forced* kernel path
    (`TIEREDSTORAGE_TPU_PALLAS*=1`) runs in Mosaic interpret mode."""
    return not on_tpu()


def run_preflight(memo: list, attempt: Callable[[], bool], what: str) -> bool:
    """Run `attempt` once and memoize a PASS into `memo` (a module-level
    list; tests clear it to re-arm the gate). `attempt` returns whether the
    kernel's output matched its reference. Any exception it raises, or a
    mismatch, raises `KernelPreflightError`; a failure is never memoized, so
    every later consult fails the same way instead of finding a quiet
    "no"."""
    if not memo:
        try:
            with ThreadPoolExecutor(1, thread_name_prefix="kernel-preflight") as own:
                ok = bool(own.submit(attempt).result())
        except Exception as exc:
            raise KernelPreflightError(
                f"{what} did not lower or run on this backend: "
                f"{type(exc).__name__}: {exc}"
            ) from exc
        if not ok:
            raise KernelPreflightError(f"{what} diverges from its reference")
        memo.append(True)
    return True
