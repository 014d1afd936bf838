"""Marginal (floor-subtracted) device-resident cost of each GCM stage.

Times each jitted stage at two sizes on device-resident inputs; the slope
gives the true per-byte cost, separating the per-launch floor.
"""

from __future__ import annotations

import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

from tieredstorage_tpu.ops import gcm
from tieredstorage_tpu.ops.aes_bitsliced import (
    aes_encrypt_planes,
    ctr_keystream_batch,
    rk_planes_from_round_keys,
)

err = lambda *a: print(*a, file=sys.stderr, flush=True)


def t(fn, *args, iters=3, warmup=1):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def run(total_mib: int, chunk_mib: int = 4):
    chunk_bytes = chunk_mib << 20
    batch = (total_mib << 20) // chunk_bytes
    key = bytes(range(32))
    ctx = gcm.make_context(key, b"aad", chunk_bytes)
    rng = np.random.default_rng(0)
    # make data genuinely device-resident (output of a jit, not device_put)
    seed_host = jax.device_put(rng.integers(0, 256, (batch, chunk_bytes), dtype=np.uint8))
    materialize = jax.jit(lambda x: x ^ np.uint8(1))
    data = jax.block_until_ready(materialize(seed_host))
    ivs = jax.block_until_ready(materialize(jax.device_put(
        rng.integers(0, 256, (batch, 12), dtype=np.uint8))))
    rk, lm, fm, cb = gcm._device_consts(ctx)
    n_blocks = ctx.n_blocks

    out = {}
    full = jax.jit(lambda r, i, d: gcm._gcm_process_batch(
        r, i, d, lm, fm, cb, chunk_bytes=chunk_bytes, n_blocks=n_blocks,
        decrypt=False))
    out["full"] = t(full, rk, ivs, data)
    ks_fn = jax.jit(lambda r, i: ctr_keystream_batch(r, i, 1, n_blocks + 1))
    out["ctr"] = t(ks_fn, rk, ivs)
    w = (batch * (n_blocks + 1) + 31) // 32
    planes = jax.block_until_ready(materialize(jax.device_put(
        rng.integers(0, 2**32, (16, 8, w), dtype=np.uint32).view(np.uint8))).view(jnp.uint32))
    rkp = rk_planes_from_round_keys(rk)
    circ = jax.jit(aes_encrypt_planes)
    out["circuit"] = t(circ, rkp, planes)
    gh = jax.jit(lambda d: gcm._ghash_of_ct(d, lm, fm, cb))
    out["ghash"] = t(gh, data)
    return out


def main():
    a_mib, b_mib = 32, 128
    ra = run(a_mib)
    rb = run(b_mib)
    err(f"{'stage':10s} {a_mib:4d}MiB(ms) {b_mib:4d}MiB(ms)  marginal GiB/s")
    for k in ra:
        slope = (rb[k] - ra[k]) / ((b_mib - a_mib) / 1024)  # s per GiB
        g = 1 / slope if slope > 0 else float("inf")
        err(f"{k:10s} {ra[k]*1e3:10.1f} {rb[k]*1e3:10.1f} {g:10.2f}")


if __name__ == "__main__":
    main()
