"""Minimal on-chip probe, smallest-compile-first, persisting results after
EVERY stage: a run cut at its time limit still leaves numbers on disk.
profile_r3.py compiles the full GCM graph as its first stage, the longest
compile of all, so this probe inverts the order: sanity (launch floor) ->
Pallas GHASH kernel -> Pallas AES kernel -> XLA circuit -> full GCM.

Usage: PYTHONPATH=. python tools/probe_min.py [out.json]
Env: PROBE_STAGES csv subset of sanity,ghash_pallas,pallas_aes,xla_ctr,
ghash_xla,full_gcm (default all), PROBE_MIB total bytes target (default 8).
"""

from __future__ import annotations

import json
import os
import sys
import time

t_start = time.monotonic()


def say(msg: str) -> None:
    print(f"[probe +{time.monotonic() - t_start:7.1f}s] {msg}", flush=True)


def main() -> None:
    out_path = sys.argv[1] if len(sys.argv) > 1 else "artifacts/probe_min.json"
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    stages = os.environ.get(
        "PROBE_STAGES",
        "sanity,ghash_pallas,pallas_aes,circuit_xla,ghash_xla,full_gcm",
    ).split(",")
    mib = int(os.environ.get("PROBE_MIB", 8))
    results: dict = {"mib": mib, "stages": {}, "t_start": time.time()}

    def persist() -> None:
        with open(out_path, "w") as f:
            json.dump(results, f, indent=1)

    say("importing jax")
    import jax
    import jax.numpy as jnp
    import numpy as np

    say(f"devices: {jax.devices()}")
    results["platform"] = jax.devices()[0].platform
    persist()

    from tieredstorage_tpu.ops import gcm
    from tieredstorage_tpu.ops.aes_bitsliced import (
        aes_encrypt_planes,
        ctr_keystream_batch,
        rk_planes_from_round_keys,
    )

    chunk_bytes = 4 << 20
    batch = max(1, (mib << 20) // chunk_bytes)
    n_bytes = batch * chunk_bytes
    key = bytes(range(32))
    rng = np.random.default_rng(0)

    def timeit(name, fn, *args, bytes_measured=n_bytes, iters=3):
        say(f"{name}: compile+first run")
        try:
            t0 = time.monotonic()
            jax.block_until_ready(fn(*args))
            compile_s = time.monotonic() - t0
            say(f"{name}: first run {compile_s:.1f}s; timing")
            best = float("inf")
            for _ in range(iters):
                t0 = time.monotonic()
                jax.block_until_ready(fn(*args))
                best = min(best, time.monotonic() - t0)
            gibs = bytes_measured / best / 2**30
            say(f"{name}: best {best * 1e3:.1f} ms = {gibs:.3f} GiB/s "
                f"(compile {compile_s:.1f}s)")
            results["stages"][name] = {
                "best_s": best, "gibs": round(gibs, 3),
                "compile_s": round(compile_s, 1),
                "bytes": bytes_measured,
            }
        except Exception as e:  # noqa: BLE001 — record, keep probing
            say(f"{name}: FAILED {e!r}"[:500])
            results["stages"][name] = {"error": repr(e)[:500]}
        persist()

    materialize = jax.jit(lambda x: x ^ np.uint8(1))

    if "sanity" in stages:
        x = jax.device_put(rng.integers(0, 256, (n_bytes,), np.uint8))
        timeit("sanity_xor", materialize, x)
        a = jax.device_put(rng.standard_normal((1024, 1024), np.float32))
        timeit("sanity_dot", jax.jit(lambda a: a @ a), a,
               bytes_measured=2 * 1024**3 // 1024)  # ~2 GFLOP marker

    ctx = gcm.make_context(key, b"aad", chunk_bytes)
    rk, lm, fm, cb = gcm._device_consts(ctx)
    n_blocks = ctx.n_blocks

    if "ghash_pallas" in stages:
        try:
            from tieredstorage_tpu.ops.ghash_pallas import (
                ROWS_PER_STEP,
                ghash_level1_pallas,
            )

            k = lm[0].shape[1]
            g = -(-n_blocks // (k // 16))
            rows = -(-batch * g // ROWS_PER_STEP) * ROWS_PER_STEP
            mat = jax.block_until_ready(
                materialize(
                    jax.device_put(rng.integers(0, 256, (rows, k), np.uint8))
                )
            )
            timeit("ghash_pallas", ghash_level1_pallas, mat, lm[0],
                   bytes_measured=rows * k)
        except Exception as e:  # noqa: BLE001
            say(f"ghash_pallas setup failed: {e!r}")
            results["stages"]["ghash_pallas"] = {"error": repr(e)[:500]}
            persist()

    rkp = None
    if "pallas_aes" in stages:
        try:
            from tieredstorage_tpu.ops.aes_pallas import (
                WORDS_PER_STEP,
                aes_encrypt_planes_pallas,
            )

            w = max(WORDS_PER_STEP, (n_bytes // 512) // WORDS_PER_STEP * WORDS_PER_STEP)
            planes = jax.block_until_ready(
                materialize(
                    jax.device_put(
                        rng.integers(0, 2**32, (16, 8, w), np.uint32).view(np.uint8)
                    )
                ).view(jnp.uint32)
            )
            rkp = jax.block_until_ready(
                jax.jit(rk_planes_from_round_keys)(jnp.asarray(rk))
            )
            timeit("pallas_aes", aes_encrypt_planes_pallas, rkp, planes,
                   bytes_measured=w * 512)
            # Cross-check AFTER the timing persists (a time limit hit during
            # the reference compile must not cost the flagship number): one
            # kernel tile vs the XLA circuit — a mistiled kernel can return
            # instantly with garbage (seen once at TSTPU_AES_R=32), and a
            # number that fails this check is not evidence.
            tile = planes[:, :, :WORDS_PER_STEP]
            got = np.asarray(aes_encrypt_planes_pallas(rkp, tile))
            ref = np.asarray(jax.jit(aes_encrypt_planes)(rkp, tile))
            if np.array_equal(got, ref):
                say("pallas_aes: output cross-checked against the XLA circuit")
                results["stages"]["pallas_aes"]["cross_check"] = "pass"
            else:
                say("pallas_aes: OUTPUT DIVERGES from the XLA circuit — "
                    "the timing above is not evidence")
                results["stages"]["pallas_aes"]["cross_check"] = "FAIL"
            persist()
        except Exception as e:  # noqa: BLE001
            say(f"pallas_aes setup failed: {e!r}")
            results["stages"]["pallas_aes"] = {"error": repr(e)[:500]}
            persist()

    if "circuit_xla" in stages or "xla_ctr" in stages:  # accept either token
        try:
            from tieredstorage_tpu.ops.aes_pallas import WORDS_PER_STEP

            if rkp is None:  # pallas_aes stage skipped or failed; cheap
                rkp = jax.block_until_ready(
                    jax.jit(rk_planes_from_round_keys)(jnp.asarray(rk))
                )
            w = max(WORDS_PER_STEP, (n_bytes // 512) // WORDS_PER_STEP * WORDS_PER_STEP)
            planes = jax.block_until_ready(
                materialize(
                    jax.device_put(
                        rng.integers(0, 2**32, (16, 8, w), np.uint32).view(np.uint8)
                    )
                ).view(jnp.uint32)
            )
            timeit("circuit_xla", jax.jit(aes_encrypt_planes), rkp, planes,
                   bytes_measured=w * 512)
        except Exception as e:  # noqa: BLE001
            say(f"circuit_xla failed: {e!r}")
            results["stages"]["circuit_xla"] = {"error": repr(e)[:500]}
            persist()

    data = ivs = None
    if "ghash_xla" in stages or "full_gcm" in stages:
        data = jax.block_until_ready(
            materialize(
                jax.device_put(
                    rng.integers(0, 256, (batch, chunk_bytes), np.uint8)
                )
            )
        )
        ivs = jax.block_until_ready(
            materialize(jax.device_put(rng.integers(0, 256, (batch, 12), np.uint8)))
        )

    if "ghash_xla" in stages:
        timeit("ghash_xla", jax.jit(lambda d: gcm._ghash_of_ct(d, lm, fm, cb)), data)

    if "full_gcm" in stages:
        full = jax.jit(
            lambda r, i, d: gcm._gcm_process_batch(
                r, i, d, lm, fm, cb,
                chunk_bytes=chunk_bytes, n_blocks=n_blocks, decrypt=False,
            )
        )
        timeit("full_gcm", full, rk, ivs, data)

    say(f"done -> {out_path}")
    results["t_end"] = time.time()
    persist()


if __name__ == "__main__":
    main()
