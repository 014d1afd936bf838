"""The loud preflight behind the Pallas kernel gates (ops/_preflight.py): on
a TPU backend a kernel that does not lower, does not run or diverges from its
reference raises; nothing is memoized as "unavailable" and nothing is
retried. Off the TPU the gates answer "no" from a platform fact read once."""

from __future__ import annotations

import pytest

from tieredstorage_tpu.ops import _preflight, aes_bitsliced, ghash_pallas
from tieredstorage_tpu.ops._preflight import KernelPreflightError, run_preflight

GATES = (
    aes_bitsliced.pallas_aes_available,
    ghash_pallas.pallas_ghash_available,
    ghash_pallas.pallas_ghash_tree_available,
)
SWITCHES = (
    "TIEREDSTORAGE_TPU_PALLAS",
    "TIEREDSTORAGE_TPU_PALLAS_GHASH",
    "TIEREDSTORAGE_TPU_PALLAS_GHASH_TREE",
)


class Attempt:
    """Counts its calls; raises, or returns the verdict it was given."""

    def __init__(self, outcome):
        self.outcome = outcome
        self.calls = 0

    def __call__(self):
        self.calls += 1
        if isinstance(self.outcome, BaseException):
            raise self.outcome
        return self.outcome


@pytest.fixture
def unforced(monkeypatch):
    for name in SWITCHES:
        monkeypatch.delenv(name, raising=False)


@pytest.fixture
def fresh_platform_fact():
    _preflight.on_tpu.cache_clear()
    yield
    _preflight.on_tpu.cache_clear()


@pytest.mark.parametrize(
    "exc",
    [
        RuntimeError("Mosaic lowering failed"),
        NotImplementedError("unsupported primitive"),
        ConnectionError("transport reset"),  # no class of failure is retried
        AssertionError("outputs differ"),
    ],
)
def test_failing_attempt_raises_without_retry_or_memo(exc):
    attempt = Attempt(exc)
    memo = []
    with pytest.raises(KernelPreflightError, match="did not lower or run") as info:
        run_preflight(memo, attempt, "test kernel")
    assert info.value.__cause__ is exc
    assert attempt.calls == 1  # one try, no retry
    assert memo == []  # a failure is never memoized as "unavailable"
    # A later consult fails the same way instead of finding a quiet "no".
    with pytest.raises(KernelPreflightError):
        run_preflight(memo, attempt, "test kernel")
    assert attempt.calls == 2


def test_divergence_raises():
    attempt = Attempt(False)
    memo = []
    with pytest.raises(KernelPreflightError, match="diverges from its reference"):
        run_preflight(memo, attempt, "test kernel")
    assert memo == []


def test_pass_is_memoized():
    attempt = Attempt(True)
    memo = []
    assert run_preflight(memo, attempt, "test kernel") is True
    assert run_preflight(memo, attempt, "test kernel") is True
    assert attempt.calls == 1
    assert memo == [True]


def test_cpu_gates_answer_no_and_probe_the_backend_once(
    monkeypatch, unforced, fresh_platform_fact
):
    import jax

    probes = []

    def backend():
        probes.append(1)
        return "cpu"

    monkeypatch.setattr(jax, "default_backend", backend)
    for _ in range(3):
        assert [gate() for gate in GATES] == [False, False, False]
        assert _preflight.interpret_off_device() is True
    assert len(probes) == 1  # a platform fact, read once


@pytest.mark.parametrize("gate", GATES + (_preflight.interpret_off_device,))
def test_backend_probe_failure_reaches_the_caller(
    monkeypatch, unforced, fresh_platform_fact, gate
):
    """If JAX cannot name its backend the caller hears about it: no gate
    answers "CPU" on its behalf, and the failure is not cached."""
    import jax

    def boom():
        raise RuntimeError("backend unavailable")

    monkeypatch.setattr(jax, "default_backend", boom)
    for _ in range(2):
        with pytest.raises(RuntimeError, match="backend unavailable"):
            gate()


@pytest.mark.parametrize(
    "gate,module,kernel,memo",
    [
        (aes_bitsliced.pallas_aes_available, "tieredstorage_tpu.ops.aes_pallas",
         "aes_encrypt_planes_pallas", (aes_bitsliced, "_PALLAS_PREFLIGHT")),
        (ghash_pallas.pallas_ghash_available, "tieredstorage_tpu.ops.ghash_pallas",
         "ghash_level1_pallas", (ghash_pallas, "_PREFLIGHT")),
        (ghash_pallas.pallas_ghash_tree_available, "tieredstorage_tpu.ops.ghash_pallas",
         "ghash_tree_pallas", (ghash_pallas, "_TREE_PREFLIGHT")),
    ],
)
def test_tpu_backend_gate_raises_when_its_kernel_fails(
    monkeypatch, unforced, gate, module, kernel, memo
):
    """The acceptance case: on a TPU backend, with a Pallas kernel made to
    fail, the gate raises instead of returning False."""
    import importlib

    def boom(*args, **kwargs):
        raise RuntimeError("Mosaic failed to compile")

    monkeypatch.setattr(_preflight, "on_tpu", lambda: True)
    monkeypatch.setattr(importlib.import_module(module), kernel, boom)
    monkeypatch.setattr(*memo, [])
    with pytest.raises(KernelPreflightError, match="Mosaic failed to compile"):
        gate()
    with pytest.raises(KernelPreflightError):  # and keeps raising
        gate()


def test_tpu_window_trace_fails_loud_instead_of_tracing_the_xla_form(
    monkeypatch, unforced
):
    """End to end: told it is on a TPU, this CPU backend can lower none of
    the kernels — so a window must fail at the first gate it consults, not
    quietly build the XLA-circuit program."""
    import numpy as np

    from tieredstorage_tpu.ops import gcm

    monkeypatch.setattr(_preflight, "on_tpu", lambda: True)
    for module, memo in (
        (aes_bitsliced, "_PALLAS_PREFLIGHT"),
        (ghash_pallas, "_PREFLIGHT"),
        (ghash_pallas, "_TREE_PREFLIGHT"),
    ):
        monkeypatch.setattr(module, memo, [])
    # 64 KiB rows: above the shape floor of every kernel.
    ctx = gcm.make_context(bytes(range(32)), b"aad", 64 << 10)
    packed = np.zeros((8, (64 << 10) + 16), np.uint8)
    with pytest.raises(KernelPreflightError, match="did not lower or run"):
        gcm.gcm_window_packed(ctx, None, packed, decrypt=False)


@pytest.mark.parametrize(
    "consult,kernel_module,kernel,memo",
    [
        # The AES circuit takes a minute in interpret mode: same mechanism,
        # kept out of the tier-1 run.
        pytest.param(
            aes_bitsliced._pallas_preflight_ok, "tieredstorage_tpu.ops.aes_pallas",
            "aes_encrypt_planes_pallas", (aes_bitsliced, "_PALLAS_PREFLIGHT"),
            marks=pytest.mark.slow,
        ),
        (ghash_pallas._preflight_ok, "tieredstorage_tpu.ops.ghash_pallas",
         "ghash_level1_pallas", (ghash_pallas, "_PREFLIGHT")),
        (ghash_pallas._tree_preflight_ok, "tieredstorage_tpu.ops.ghash_pallas",
         "ghash_tree_pallas", (ghash_pallas, "_TREE_PREFLIGHT")),
    ],
)
def test_real_kernel_preflight_passes_when_consulted_mid_trace(
    monkeypatch, consult, kernel_module, kernel, memo
):
    """What the first run on the chip raised (PR 21): the preflight ran the
    kernel under `jax.ensure_compile_time_eval`, where a Pallas kernel cannot
    be traced at all, and the old gate filed that as "kernel unavailable".
    Here the REAL kernel — in interpret mode, the one thing the CPU changes —
    goes through the real attempt, consulted where production consults it:
    inside the caller's jit trace."""
    import functools
    import importlib

    import jax
    import jax.numpy as jnp

    module = importlib.import_module(kernel_module)
    monkeypatch.setattr(
        module, kernel, functools.partial(getattr(module, kernel), interpret=True)
    )
    monkeypatch.setattr(*memo, [])
    verdicts = []

    @jax.jit
    def traced(x):
        verdicts.append(consult())
        return x + 1

    traced(jnp.zeros(4))
    assert verdicts == [True]


def test_forced_paths_interpret_off_the_tpu():
    """Both forced-kernel call sites take their interpret flag from the one
    platform fact."""
    import inspect

    from tieredstorage_tpu.ops import gcm

    assert "interpret_off_device" in inspect.getsource(
        aes_bitsliced.ctr_keystream_batch
    )
    assert inspect.getsource(gcm._ghash_grouped).count("interpret_off_device") == 2


def test_gate_modules_share_the_machinery():
    """All three kernel gates route through run_preflight, so the loud
    contract cannot diverge between them."""
    import inspect

    for fn in (
        aes_bitsliced._pallas_preflight_ok,
        ghash_pallas._preflight_ok,
        ghash_pallas._tree_preflight_ok,
    ):
        assert "run_preflight" in inspect.getsource(fn)
