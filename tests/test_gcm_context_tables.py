"""A segment key's GCM contexts from one H-power table (ISSUE 28).

`ops/gcm.py` builds a key's tables once, vectorised, and assembles every
size's context from slices of them. The scalar builder below is the one the
module had before (`gf128.ghash_agg_matrices`, `mult_matrix`,
`ghash_step_matrix`, H from the device cipher): every array of both context
kinds has to equal its output bit for bit, so no window program sees a
difference. Single flight and the build's cost are held here too.
"""

from __future__ import annotations

import functools
import os
import sys
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from tieredstorage_tpu.ops import gcm, gf128
from tieredstorage_tpu.ops.aes import (
    aes_encrypt_block_host,
    aes_encrypt_blocks,
    key_expansion,
)

KEY = bytes(range(7, 39))
#: one block, under and at level 1's width, a 2100-block index, the benchmark
#: cells' ragged last chunk, a whole 4 MiB chunk
SIZES = [16, 1000, 2047, 2048, 33600, 3894304, 4194304]
AADS = [b"", bytes(range(40))]


def _device_h(key: bytes) -> int:
    block = np.asarray(
        aes_encrypt_blocks(jnp.asarray(key_expansion(key)), jnp.zeros((1, 16), jnp.uint8))
    )[0]
    return int.from_bytes(block.tobytes(), "big")


@functools.lru_cache(maxsize=None)
def _scalar_agg_mats(m: int) -> tuple:
    return gf128.ghash_agg_matrices(_device_h(KEY), m)


def _scalar_context(aad: bytes, chunk_bytes: int) -> gcm.GcmContext:
    h = _device_h(KEY)
    m_c = -(-chunk_bytes // 16)
    agg_mats = _scalar_agg_mats(m_c)
    aad_blocks = [aad[i : i + 16] for i in range(0, len(aad), 16)]
    t_a = 0
    for i, blk in enumerate(aad_blocks):
        power = gf128.gcm_pow(h, len(aad_blocks) - 1 - i)
        t_a ^= gf128.gcm_mult(int.from_bytes(blk.ljust(16, b"\x00"), "big"), power)
    len_block = int.from_bytes(
        (len(aad) * 8).to_bytes(8, "big") + (chunk_bytes * 8).to_bytes(8, "big"), "big"
    )
    const = gf128.gcm_mult(t_a, gf128.gcm_pow(h, m_c + 2)) ^ gf128.gcm_mult(len_block, h)
    return gcm.GcmContext(
        round_keys=key_expansion(KEY),
        agg_mats=agg_mats,
        final_mat=np.ascontiguousarray(
            gf128.mult_matrix(gf128.gcm_mult(h, h)).T.astype(np.int8)
        ),
        const_bits=gf128.int_to_bitvec(const),
        chunk_bytes=chunk_bytes,
        n_blocks=m_c,
        step_mat=gf128.ghash_step_matrix(h, agg_mats[0].shape[1] // 16),
    )


def _scalar_varlen_context(aad: bytes, max_bytes: int) -> gcm.GcmVarlenContext:
    h = _device_h(KEY)
    m_max = -(-max_bytes // 16)
    m_a = -(-len(aad) // 16)
    agg_mats = _scalar_agg_mats(m_a + m_max + 1)
    return gcm.GcmVarlenContext(
        round_keys=key_expansion(KEY),
        aad_blocks=np.frombuffer(aad.ljust(m_a * 16, b"\x00"), np.uint8).reshape(m_a, 16),
        agg_mats=agg_mats,
        h_mat=np.ascontiguousarray(gf128.mult_matrix(h).T.astype(np.int8)),
        aad_bit_len=len(aad) * 8,
        max_bytes=max_bytes,
        m_max=m_max,
        m_cap=m_a + m_max + 1,
        step_mat=gf128.ghash_step_matrix(h, agg_mats[0].shape[1] // 16),
    )


def _assert_same(got, want):
    assert type(got) is type(want)
    for name in (f.name for f in got.__dataclass_fields__.values()):
        a, b = getattr(got, name), getattr(want, name)
        if name == "agg_mats":
            assert len(a) == len(b), name
            pairs = zip(a, b)
        elif isinstance(b, np.ndarray):
            pairs = [(a, b)]
        else:
            assert a == b and type(a) is type(b), name
            continue
        for x, y in pairs:
            assert x.dtype == y.dtype and x.shape == y.shape, name
            assert x.flags.c_contiguous and np.array_equal(x, y), name


@pytest.mark.parametrize("aad", AADS, ids=["no-aad", "aad40"])
@pytest.mark.parametrize("size", SIZES)
@pytest.mark.parametrize("kind", ["fixed", "varlen"])
def test_table_built_context_equals_the_scalar_builders(kind, size, aad):
    if kind == "fixed":
        _assert_same(gcm.make_context(KEY, aad, size), _scalar_context(aad, size))
    else:
        _assert_same(
            gcm.make_varlen_context(KEY, aad, size),
            _scalar_varlen_context(aad, gcm.bucket_max_bytes(size)),
        )


def test_vectorised_matrices_equal_the_scalar_ones():
    elements = [int.from_bytes(os.urandom(16), "big") for _ in range(5)]
    elements += [1, 1 << 127, (1 << 128) - 1]
    got = gf128.mult_matrices_t(elements)
    assert got.dtype == np.int8 and got.shape == (len(elements), 128, 128)
    for matrix, element in zip(got, elements):
        assert np.array_equal(matrix, gf128.mult_matrix(element).T)


@pytest.mark.parametrize("key,block,expected", [
    # FIPS-197 Appendix C.3
    (bytes(range(32)).hex(), "00112233445566778899aabbccddeeff",
     "8ea2b7ca516745bfeafc49904b496089"),
    # H of the GCM specification's AES-256 test cases 13-14 and 15-18
    ("00" * 32, "00" * 16, "dc95c078a2408989ad48a21492842087"),
    ("feffe9928665731c6d6a8f9467308308" * 2, "00" * 16,
     "acbef20579b4b8ebce889bac8732dad7"),
    (os.urandom(32).hex(), os.urandom(16).hex(), None),
], ids=["fips197-c3", "gcm-tc13", "gcm-tc16", "random"])
def test_host_block_cipher_matches_the_standard_and_the_device_cipher(key, block, expected):
    round_keys = key_expansion(bytes.fromhex(key))
    plain = np.frombuffer(bytes.fromhex(block), np.uint8)
    got = aes_encrypt_block_host(round_keys, plain)
    assert got.dtype == np.uint8 and got.shape == (16,)
    if expected is not None:
        assert got.tobytes().hex() == expected
    device = np.asarray(aes_encrypt_blocks(jnp.asarray(round_keys), jnp.asarray(plain[None])))
    assert np.array_equal(got, device[0])


def test_a_table_is_read_only_and_shared_by_its_contexts():
    key = os.urandom(32)
    whole = gcm.make_context(key, b"aad", 4194304)
    ragged = gcm.make_context(key, b"aad", 3894304)
    for level in range(3):  # the ragged chunk's last level is the tail of the whole one's
        assert np.shares_memory(whole.agg_mats[level], ragged.agg_mats[level])
    with pytest.raises(ValueError):
        whole.agg_mats[0][0, 0, 0] = 1


def _delta(before: dict) -> dict:
    return {k: v - before[k] for k, v in gcm.context_stats().items()}


def _in_threads(n, target):
    """`target(i)` in n threads released together; what each returned or raised."""
    start, results = threading.Barrier(n, timeout=60), [None] * n

    def run(i):
        start.wait()
        try:
            results[i] = target(i)
        except Exception as e:  # noqa: BLE001 - handed to the assertions
            results[i] = e

    threads = [threading.Thread(target=run, args=(i,)) for i in range(n)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # interleave the threads inside the caches' critical sections
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    return results


@pytest.mark.parametrize("make", [gcm.make_context, gcm.make_varlen_context])
def test_first_uses_of_one_key_aad_and_size_run_one_build(make, monkeypatch):
    real = gf128.ghash_level_table

    def slow(p):
        time.sleep(0.05)  # the others arrive while the build runs
        return real(p)

    monkeypatch.setattr(gf128, "ghash_level_table", slow)
    key, before = os.urandom(32), gcm.context_stats()
    contexts = _in_threads(8, lambda i: make(key, b"aad", 40000))
    assert all(c is contexts[0] for c in contexts) and not isinstance(contexts[0], Exception)
    delta = _delta(before)
    assert delta["context_build_seconds"] > 0
    assert {k: v for k, v in delta.items() if k != "context_build_seconds"} == {
        "context_builds": 1, "context_builds_duplicate": 0,
        "key_tables_built": 1, "key_table_hits": 0,
    }
    assert not gcm._BUILDS_IN_FLIGHT


def test_first_uses_of_one_key_at_many_sizes_build_one_table():
    key, before = os.urandom(32), gcm.context_stats()
    contexts = _in_threads(8, lambda i: gcm.make_context(key, b"aad", 4096 * (i + 1)))
    assert len({id(c) for c in contexts}) == 8
    assert all(np.shares_memory(c.agg_mats[0], contexts[0].agg_mats[0]) for c in contexts)
    delta = _delta(before)
    assert (delta["context_builds"], delta["context_builds_duplicate"]) == (8, 0)
    assert (delta["key_tables_built"], delta["key_table_hits"]) == (1, 7)


@pytest.mark.parametrize("make", [gcm.make_context, gcm.make_varlen_context])
def test_a_build_that_raises_releases_its_waiters_and_leaves_no_entry(make, monkeypatch):
    calls = []

    def broken(p):
        calls.append(p)
        time.sleep(0.2)  # the waiters are in by now
        raise RuntimeError("no table")

    monkeypatch.setattr(gf128, "ghash_level_table", broken)
    key, before = os.urandom(32), gcm.context_stats()
    errors = _in_threads(8, lambda i: make(key, b"aad", 40000))
    assert all(isinstance(e, RuntimeError) and "no table" in str(e) for e in errors)
    # one build unless a thread came after the failure had cleared the entry
    assert 1 <= len(calls) < 8
    assert _delta(before)["context_builds"] == len(calls)
    assert not gcm._BUILDS_IN_FLIGHT
    monkeypatch.undo()
    before = gcm.context_stats()
    context = make(key, b"aad", 40000)
    assert context is make(key, b"aad", 40000)
    delta = _delta(before)
    assert (delta["context_builds"], delta["key_tables_built"]) == (1, 0)
    assert (delta["context_builds_duplicate"], delta["key_table_hits"]) == (0, 1)


def test_a_fresh_keys_six_contexts_build_within_budget():
    """The `kip405-aes` copy's sizes: a whole chunk, the ragged last one, and
    the offset, time, producer-snapshot and leader-epoch sections. The scalar
    builder took ~1.6 s for them on this machine; the tables ~0.03 s."""
    key, before = os.urandom(32), gcm.context_stats()
    start = time.perf_counter()
    for size in (4194304, 3894304, 523696, 785544, 96, 8):
        gcm.make_context(key, b"aad", size)
    assert time.perf_counter() - start < 0.5
    delta = _delta(before)
    assert (delta["context_builds"], delta["key_tables_built"], delta["key_table_hits"]) == (6, 1, 5)
