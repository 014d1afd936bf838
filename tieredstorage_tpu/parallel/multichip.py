"""Production-path multi-chip oracle drill.

ONE implementation, three consumers — the driver's ``dryrun_multichip``
entry point, the ``make multichip-demo`` CI gate, and the test suite — so
the multi-chip proof and the serving path can never drift again: every
sharded byte here is produced by the REAL transform pipeline
(``TpuTransformBackend._build_packed`` → row-sharded ``_stage_packed`` →
fused ``_launch_packed`` under shard_map → ``_encrypt_finish``), not by a
parallel reimplementation.

The drill asserts, for fixed-size AND variable-length windows:

- **Byte parity**: the sharded backend's wire bytes (IV || ct || tag per
  chunk) equal the unsharded backend's, encrypt and decrypt.
- **Round trip**: sharded decrypt returns the original chunks (and the
  decrypt direction also fans out across the mesh).
- **Dispatch accounting**: one logical fused dispatch, one h2d staging
  transfer, one d2h fetch per window at ``mesh_size == n_devices``, with
  every staged buffer donated back to XLA (one HBM allocation per
  in-flight window).
- **Non-divisible batches**: a row count not divisible by the mesh size
  pads on the host and the padding never reaches the wire.
- **Chunk-index collective**: the per-row transformed sizes all-gathered
  over the mesh (plus a psum of total bytes) agree with the host-side
  sizes the manifest records — the collective the chunk-index build needs
  when a segment's rows span chips.
- **Host oracle** (when ``cryptography`` is importable): row 0 of the
  fixed window equals the reference AES-256-GCM implementation.

Callers must already be on a platform with >= n_devices devices (tests:
conftest's 8-device virtual CPU mesh; tools: ``pin_virtual_cpu``).
"""

from __future__ import annotations

import os
from typing import Optional

import numpy as np

from tieredstorage_tpu.parallel.mesh import DATA_AXIS, MeshPlan


def _det_ivs(n: int) -> list:
    from tieredstorage_tpu.security.aes import IV_SIZE

    return [(i + 1).to_bytes(4, "big") * (IV_SIZE // 4) for i in range(n)]


def _fresh_backend(mesh_spec):
    from tieredstorage_tpu.transform.tpu import TpuTransformBackend

    backend = TpuTransformBackend()
    backend.configure({"mesh.devices": mesh_spec})
    return backend


def _index_collective(plan: MeshPlan, wire_sizes: list) -> dict:
    """All-gather the per-row transformed sizes (and psum the total) over
    the mesh — what the chunk-index build needs when rows span chips —
    and check them against the host-side sizes the manifest records."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = plan.mesh
    sizes = np.asarray(wire_sizes, np.int32)
    pad = plan.pad_rows(len(sizes))
    padded = np.concatenate([sizes, np.zeros(pad, np.int32)])

    def step(local_sizes):
        all_sizes = jax.lax.all_gather(local_sizes, DATA_AXIS, tiled=True)
        total = jax.lax.psum(jnp.sum(local_sizes), DATA_AXIS)
        return all_sizes, total

    gathered, total = jax.jit(
        jax.shard_map(
            step, mesh=mesh, in_specs=(P(DATA_AXIS),),
            out_specs=(P(None), P()), check_vma=False,
        )
    )(jax.device_put(padded, NamedSharding(mesh, P(DATA_AXIS))))
    ok = bool(
        np.array_equal(np.asarray(gathered)[: len(sizes)], sizes)
        and int(total) == int(sizes.sum())
    )
    return {"ok": ok, "total_bytes": int(total), "rows": len(sizes)}


def _window_report(chunks, plan, sharded, unsharded, opts, d_opts) -> tuple:
    from tieredstorage_tpu.ops import gcm as gcm_ops

    ops_before = gcm_ops.device_dispatches()
    sharded.reset_dispatch_stats()
    wire_sharded = sharded.transform(chunks, opts)
    enc_stats = sharded.reset_dispatch_stats()
    ops_launches = gcm_ops.device_dispatches() - ops_before

    wire_plain = unsharded.transform(chunks, opts)
    back = sharded.detransform(wire_sharded, d_opts)
    dec_stats = sharded.reset_dispatch_stats()

    n_rows = len(chunks)
    report = {
        "rows": n_rows,
        "bytes_in": sum(len(c) for c in chunks),
        "mesh_size": enc_stats.mesh_size,
        "rows_per_device": enc_stats.rows_per_device,
        "pad_rows": plan.pad_rows(n_rows),
        "dispatches_per_window": enc_stats.dispatches_per_window,
        "checks": {
            "sharded_vs_unsharded_byte_parity": wire_sharded == wire_plain,
            "sharded_decrypt_roundtrip": back == list(chunks),
            "one_logical_dispatch": (
                enc_stats.windows == 1
                and enc_stats.dispatches == ops_launches == 1
                and enc_stats.h2d_transfers == enc_stats.d2h_fetches == 1
            ),
            "dispatch_fanned_out_over_mesh": enc_stats.mesh_size == plan.size,
            "staged_buffer_donated": (
                enc_stats.donated_buffers == enc_stats.windows
                and dec_stats.donated_buffers == dec_stats.windows
            ),
            "decrypt_fanned_out_over_mesh": dec_stats.mesh_size == plan.size,
        },
    }
    wire_sizes = [len(c) for c in wire_sharded]
    report["index_collective"] = _index_collective(plan, wire_sizes)
    report["checks"]["chunk_index_collective"] = report["index_collective"]["ok"]
    return report, wire_sharded


def run_drill(
    n_devices: int = 8,
    *,
    chunk_bytes: Optional[int] = None,
    window: Optional[int] = None,
) -> dict:
    """Run the production-path multi-chip drill; returns the report dict
    (``report["ok"]`` aggregates every check).

    Shapes default to the driver's 4 MiB x 64-row windows, shrinkable via
    ``TSTPU_DRYRUN_CHUNK_BYTES`` / ``TSTPU_DRYRUN_WINDOW`` (the CI demo and
    the tests pass small explicit shapes).
    """
    from tieredstorage_tpu.security.aes import AesEncryptionProvider
    from tieredstorage_tpu.transform.api import DetransformOptions, TransformOptions

    if chunk_bytes is None:
        chunk_bytes = int(os.environ.get("TSTPU_DRYRUN_CHUNK_BYTES", 4 << 20))
    if window is None:
        window = int(os.environ.get("TSTPU_DRYRUN_WINDOW", 64))

    plan = MeshPlan.from_spec(n_devices)
    if plan.size != n_devices:
        raise RuntimeError(
            f"mesh plan resolved to {plan.size} devices, wanted {n_devices} "
            "(pin the virtual CPU mesh before running the drill)"
        )
    sharded = _fresh_backend(n_devices)
    unsharded = _fresh_backend(1)

    dk = AesEncryptionProvider.create_data_key_and_aad()
    rng = np.random.default_rng(42)

    report: dict = {
        "n_devices": n_devices,
        "mesh_shape": plan.describe(),
        "chunk_bytes": chunk_bytes,
    }

    # ---- fixed-size window, batch divisible by the mesh.
    fixed_rows = max(n_devices, window - window % n_devices)
    chunks = [
        rng.integers(0, 256, chunk_bytes, np.uint8).tobytes()
        for _ in range(fixed_rows)
    ]
    ivs = _det_ivs(fixed_rows)
    opts = TransformOptions(encryption=dk, ivs=ivs)
    d_opts = DetransformOptions(encryption=dk)
    report["fixed"], wire_fixed = _window_report(
        chunks, plan, sharded, unsharded, opts, d_opts
    )

    # Host AES-256-GCM oracle on row 0 (cryptography is optional off-CI).
    try:
        expected = AesEncryptionProvider.encrypt_chunk(
            chunks[0], dk.data_key, dk.aad, iv=ivs[0]
        )
        report["fixed"]["checks"]["host_oracle_row0"] = wire_fixed[0] == expected
    except ModuleNotFoundError as exc:
        report["host_oracle_skipped"] = f"{exc}"

    # ---- varlen window with a NON-divisible batch: padding rows are added
    # on the host, sharded with everything else, and never reach the wire.
    varlen_rows = n_devices + max(3, n_devices // 2)  # never divisible
    if varlen_rows % n_devices == 0:
        varlen_rows += 1
    sizes = rng.integers(max(1, chunk_bytes // 7), chunk_bytes, varlen_rows)
    sizes[-1] = max(1, int(sizes[-1]) % 37)  # short tail chunk
    vchunks = [
        rng.integers(0, 256, int(s), np.uint8).tobytes() for s in sizes
    ]
    v_opts = TransformOptions(encryption=dk, ivs=_det_ivs(varlen_rows))
    report["varlen"], _ = _window_report(
        vchunks, plan, sharded, unsharded, v_opts, d_opts
    )
    report["varlen"]["checks"]["batch_padding_exercised"] = (
        report["varlen"]["pad_rows"] > 0
    )

    checks = dict(report["fixed"]["checks"])
    checks.update({f"varlen_{k}": v for k, v in report["varlen"]["checks"].items()})
    report["ok"] = all(checks.values())
    report["failed_checks"] = sorted(k for k, v in checks.items() if not v)
    return report


def summary_line(report: dict) -> str:
    """One artifact-tail line in the historical dryrun flavor."""
    fixed, varlen = report["fixed"], report["varlen"]
    return (
        f"[dryrun_multichip] production-path n_devices={report['n_devices']} "
        f"mesh={report['mesh_shape']} chunk_bytes={report['chunk_bytes']} "
        f"fixed_rows={fixed['rows']} varlen_rows={varlen['rows']} "
        f"(pad={varlen['pad_rows']}) "
        f"dispatches_per_window={fixed['dispatches_per_window']} "
        f"rows_per_device={fixed['rows_per_device']} "
        f"collectives=all_gather+psum "
        f"total_wire_bytes={fixed['index_collective']['total_bytes']} "
        f"oracle={'pass' if report['ok'] else 'FAIL:' + ','.join(report['failed_checks'])}"
    )
