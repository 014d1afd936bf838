"""The fused Pallas AES kernel must be bit-exact vs the XLA circuit.

The kernel body (ShiftRows-fused slicing, stacked S-box, MixColumns variable
wiring, SMEM round-key XORs) is verified on every run by tracing it with
plain-array stand-ins for the refs — identical math, no Mosaic/interpreter in
the loop. The full `pallas_call` plumbing (grid, BlockSpecs, SMEM) runs under
Mosaic's interpreter only when TIEREDSTORAGE_SLOW_TESTS=1: XLA-CPU takes ~8
minutes to compile the interpreted kernel (the real-TPU Mosaic compile is
what tests/test_tpu_compile.py and chip_smoke.py exercise).
"""

from __future__ import annotations

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tieredstorage_tpu.ops import aes_pallas
from tieredstorage_tpu.ops.aes_bitsliced import (
    aes_encrypt_planes,
    make_rk_planes,
)

KEY = bytes(range(32))


class _ArrayRef:
    """Read-only stand-in for a Pallas ref backed by a traced array."""

    def __init__(self, arr):
        self._arr = arr

    def __getitem__(self, idx):
        return self._arr[idx]


class _CollectRef:
    """Write-only stand-in collecting kernel outputs."""

    def __init__(self):
        self.out = {}

    def __setitem__(self, idx, val):
        self.out[idx] = val


def _run_kernel_body(rk2d, st4):
    out_ref = _CollectRef()
    aes_pallas._aes_kernel(_ArrayRef(rk2d), _ArrayRef(st4), out_ref)
    rows = [
        jnp.stack([out_ref.out[(p, b)] for b in range(8)], axis=0) for p in range(16)
    ]
    return jnp.stack(rows, axis=0)


def test_kernel_body_matches_xla_circuit():
    rng = np.random.default_rng(1)
    rk = jnp.asarray(make_rk_planes(KEY))
    w = aes_pallas.WORDS_PER_STEP
    state = jnp.asarray(rng.integers(0, 2**32, (16, 8, w), dtype=np.uint32))

    expected = np.asarray(jax.jit(aes_encrypt_planes)(rk, state))
    # Eager on purpose: XLA-CPU takes minutes to compile the 10k-op body as
    # one graph, but executes it op-by-op in ~1 s.
    got = np.asarray(
        _run_kernel_body(rk.reshape(15, 128), state.reshape(16, 8, aes_pallas.R, 128))
    ).reshape(16, 8, w)
    np.testing.assert_array_equal(got, expected)


def test_kernel_body_multi_step_tiling():
    """Two grid steps' worth of words, each evaluated independently."""
    rng = np.random.default_rng(2)
    rk = jnp.asarray(make_rk_planes(KEY))
    w = aes_pallas.WORDS_PER_STEP
    state = jnp.asarray(rng.integers(0, 2**32, (16, 8, 2 * w), dtype=np.uint32))
    expected = np.asarray(jax.jit(aes_encrypt_planes)(rk, state))
    for step in range(2):
        sl = state[:, :, step * w : (step + 1) * w]
        got = np.asarray(
            _run_kernel_body(rk.reshape(15, 128), sl.reshape(16, 8, aes_pallas.R, 128))
        ).reshape(16, 8, w)
        np.testing.assert_array_equal(got, expected[:, :, step * w : (step + 1) * w])


def test_pallas_call_interpret_end_to_end_subprocess():
    """The full `pallas_call` plumbing of `_aes_kernel` — grid, BlockSpecs,
    SMEM round keys — must EXECUTE in CI, not only the traced kernel body
    (round-3 VERDICT weak 7: the call path had run zero times anywhere).
    XLA-CPU needs ~8 min to optimize the ~10k-op interpreted kernel; with
    --xla_backend_optimization_level=0 it compiles in ~2.5 min, and the flag
    must be set before backend init, hence the subprocess."""
    import subprocess
    import sys

    script = """
from tieredstorage_tpu.utils.platforms import pin_virtual_cpu
pin_virtual_cpu(1)
import numpy as np
import jax, jax.numpy as jnp
from tieredstorage_tpu.ops import aes_pallas
from tieredstorage_tpu.ops.aes_bitsliced import aes_encrypt_planes, make_rk_planes

rng = np.random.default_rng(3)
rk = jnp.asarray(make_rk_planes(bytes(range(32))))
state = jnp.asarray(
    rng.integers(0, 2**32, (16, 8, aes_pallas.WORDS_PER_STEP), dtype=np.uint32)
)
got = np.asarray(aes_pallas.aes_encrypt_planes_pallas(rk, state, interpret=True))
expected = np.asarray(jax.jit(aes_encrypt_planes)(rk, state))
np.testing.assert_array_equal(got, expected)
print("PALLAS_CALL_OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_backend_optimization_level=0"
    ).strip()
    env.pop("JAX_PLATFORMS", None)
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=540,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "PALLAS_CALL_OK" in proc.stdout


@pytest.mark.skipif(
    os.environ.get("TIEREDSTORAGE_SLOW_TESTS") != "1",
    reason="fully-optimized interpret compile takes ~8 min on XLA-CPU",
)
def test_pallas_call_interpret_end_to_end():
    rng = np.random.default_rng(3)
    rk = jnp.asarray(make_rk_planes(KEY))
    w = aes_pallas.WORDS_PER_STEP
    state = jnp.asarray(rng.integers(0, 2**32, (16, 8, w), dtype=np.uint32))
    expected = np.asarray(jax.jit(aes_encrypt_planes)(rk, state))
    got = np.asarray(aes_pallas.aes_encrypt_planes_pallas(rk, state, interpret=True))
    np.testing.assert_array_equal(got, expected)


def test_keystream_pallas_gate_defaults_off_on_cpu(monkeypatch):
    """On the CPU backend the XLA circuit is used unless explicitly forced."""
    monkeypatch.delenv("TIEREDSTORAGE_TPU_PALLAS", raising=False)
    from tieredstorage_tpu.ops.aes_bitsliced import _use_pallas_circuit

    assert jax.default_backend() == "cpu"
    assert not _use_pallas_circuit(1 << 20)
    monkeypatch.setenv("TIEREDSTORAGE_TPU_PALLAS", "1")
    assert _use_pallas_circuit(8)
    monkeypatch.setenv("TIEREDSTORAGE_TPU_PALLAS", "0")
    assert not _use_pallas_circuit(1 << 20)


class TestForcedPathCrosscheck:
    """TIEREDSTORAGE_TPU_PALLAS=1 bypasses the preflight, so the forced
    gate must run the TSTPU_AES_R OUTPUT cross-check itself (not just the
    import-time range check): a behaviorally mistiled kernel body has to
    fail loud at first use, never corrupt keystream silently."""

    def test_forced_gate_runs_and_memoizes_the_crosscheck(self, monkeypatch):
        from tieredstorage_tpu.ops import aes_bitsliced, aes_pallas

        monkeypatch.setenv("TIEREDSTORAGE_TPU_PALLAS", "1")
        monkeypatch.setattr(aes_bitsliced, "_FORCED_CROSSCHECK", [])
        calls = []
        real = aes_pallas.kernel_body_reference

        def counting(rk, state):
            calls.append(1)
            return real(rk, state)

        monkeypatch.setattr(aes_pallas, "kernel_body_reference", counting)
        assert aes_bitsliced._use_pallas_circuit(8)
        assert aes_bitsliced._use_pallas_circuit(1 << 20)
        # One cross-check per process, verdict memoized.
        assert len(calls) == 1

    def test_mistiled_kernel_fails_loud_not_silent(self, monkeypatch):
        """A kernel body whose output diverges (what a mistiled R produces)
        must raise on the forced path — NOT return False and quietly fall
        back, and NOT return True and corrupt keystream."""
        from tieredstorage_tpu.ops import aes_bitsliced, aes_pallas

        monkeypatch.setenv("TIEREDSTORAGE_TPU_PALLAS", "1")
        monkeypatch.setattr(aes_bitsliced, "_FORCED_CROSSCHECK", [])
        real = aes_pallas.kernel_body_reference
        monkeypatch.setattr(
            aes_pallas,
            "kernel_body_reference",
            lambda rk, state: real(rk, state) ^ jnp.uint32(1),  # one flipped bit
        )
        with pytest.raises(RuntimeError, match="diverges"):
            aes_bitsliced._use_pallas_circuit(8)
        # The bad verdict stays memoized: every later use keeps failing loud.
        with pytest.raises(RuntimeError, match="diverges"):
            aes_bitsliced._use_pallas_circuit(1 << 20)

    def test_kernel_body_reference_matches_circuit(self):
        """The shared evaluator the cross-check runs is itself bit-exact
        against the XLA circuit on the configured R."""
        from tieredstorage_tpu.ops import aes_pallas
        from tieredstorage_tpu.ops.aes_bitsliced import aes_encrypt_planes

        rng = np.random.default_rng(9)
        rk = jnp.asarray(make_rk_planes(KEY))
        w = aes_pallas.WORDS_PER_STEP
        state = jnp.asarray(rng.integers(0, 2**32, (16, 8, w), dtype=np.uint32))
        got = np.asarray(aes_pallas.kernel_body_reference(rk, state))
        expected = np.asarray(jax.jit(aes_encrypt_planes)(rk, state))
        np.testing.assert_array_equal(got, expected)


def test_preflight_failure_raises_instead_of_degrading(monkeypatch):
    """A Mosaic lowering/runtime failure must RAISE: the XLA circuit never
    takes the kernel's place in silence, and the failure is not memoized as
    a quiet "unavailable" (ops/_preflight.py)."""
    from tieredstorage_tpu.ops import aes_bitsliced, aes_pallas
    from tieredstorage_tpu.ops._preflight import KernelPreflightError

    def boom(*a, **k):
        raise RuntimeError("mosaic lowering failed")

    monkeypatch.setattr(aes_pallas, "aes_encrypt_planes_pallas", boom)
    monkeypatch.setattr(aes_bitsliced, "_PALLAS_PREFLIGHT", [])
    with pytest.raises(KernelPreflightError, match="mosaic lowering failed"):
        aes_bitsliced._pallas_preflight_ok()
    assert aes_bitsliced._PALLAS_PREFLIGHT == []
    with pytest.raises(KernelPreflightError):
        aes_bitsliced._pallas_preflight_ok()


def test_preflight_works_under_a_jit_trace(monkeypatch):
    """The gate is consulted while the caller's jit is TRACING; omnistaging
    must not turn the verdict into a TracerBoolConversionError that fails
    the gate on healthy TPUs."""
    from tieredstorage_tpu.ops import aes_bitsliced, aes_pallas

    # Stand-in "kernel" that is definitionally correct (the XLA circuit),
    # so a healthy platform must yield ok=True even mid-trace.
    monkeypatch.setattr(
        aes_pallas,
        "aes_encrypt_planes_pallas",
        lambda rk, state, **kw: aes_bitsliced.aes_encrypt_planes(rk, state),
    )
    monkeypatch.setattr(aes_bitsliced, "_PALLAS_PREFLIGHT", [])

    verdicts = []

    @jax.jit
    def traced(x):
        verdicts.append(aes_bitsliced._pallas_preflight_ok())
        return x + 1

    traced(jnp.zeros(4))
    assert verdicts == [True]


class TestTstpuAesRValidation:
    """TSTPU_AES_R mis-tiles the ShiftRows un-stack silently on the
    TIEREDSTORAGE_TPU_PALLAS=1 forced path (no preflight cross-check runs
    there), so the override must be validated at import: power of two in
    [8, 256] or fail loud."""

    @pytest.mark.parametrize("r", ["8", "16", "32", "64", "128", "256"])
    def test_valid_tiles_accepted(self, r):
        from tieredstorage_tpu.ops.aes_pallas import _validated_r

        assert _validated_r(r) == int(r)

    @pytest.mark.parametrize("r", ["12", "24", "0", "4", "-8", "512", "7", "x", "8.0"])
    def test_mistiled_r_rejected(self, r):
        from tieredstorage_tpu.ops.aes_pallas import _validated_r

        with pytest.raises(ValueError):
            _validated_r(r)


@pytest.mark.parametrize("step", [0, 1])
def test_keyed_kernel_body_takes_its_steps_key(monkeypatch, step):
    """`_aes_keyed_kernel` under the scalar-prefetched step -> slot map: the
    grid step's own round keys, read from the launch's stacked table."""
    rng = np.random.default_rng(4 + step)
    keys = [bytes(range(32)), bytes(range(1, 33)), bytes(range(2, 34))]
    table = jnp.stack([jnp.asarray(make_rk_planes(k)) for k in keys])  # [3, 15, 16, 8]
    step_keys = jnp.asarray([2, 0], jnp.int32)
    w = aes_pallas.WORDS_PER_STEP
    state = jnp.asarray(rng.integers(0, 2**32, (16, 8, w), dtype=np.uint32))
    monkeypatch.setattr(aes_pallas.pl, "program_id", lambda axis: step)
    out_ref = _CollectRef()
    aes_pallas._aes_keyed_kernel(
        _ArrayRef(step_keys), _ArrayRef(table.reshape(-1, 128)),
        _ArrayRef(state.reshape(16, 8, aes_pallas.R, 128)), out_ref,
    )
    got = jnp.stack([
        jnp.stack([out_ref.out[(p, b)] for b in range(8)]) for p in range(16)
    ]).reshape(16, 8, w)
    slot = int(step_keys[step])
    expected = np.asarray(jax.jit(aes_encrypt_planes)(table[slot], state))
    np.testing.assert_array_equal(np.asarray(got), expected)
