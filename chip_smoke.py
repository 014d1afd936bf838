#!/usr/bin/env python3
"""Chip smoke: the served copy/fetch path, once, on the TPU, at real size.

One process builds a `RemoteStorageManager` behind a `SidecarHttpGateway`
the way `tieredstorage_tpu/sidecar/server.py:main` does, configured as the
deployment BASELINE.md names (configs 1-2: 4 MiB chunks, filesystem backend,
AES-256-GCM with a fresh RSA key pair, the TPU transform backend named
explicitly, a 4 GiB device hot tier), and drives it through the gateway's own
routes (`/v1/copy`, `/v1/fetch`, `/v1/fetch-index`, `/v1/delete`):

- copies a seeded segment of 1 GiB less 300 000 bytes (Kafka's default
  `log.segment.bytes`, chunk-unaligned) with compression off, then one with
  zstd on;
- holds both uploads to the plain reference: a second RSM over the same
  store with `CpuTransformBackend` (`cryptography` AESGCM + `zstandard`)
  must read the stored objects back to the source bytes, and a segment that
  the reference uploaded must read back through the TPU backend;
- answers ranged fetches byte-compared with the source, repeats one until
  the device hot tier serves it with zero new GCM dispatches, fetches an
  index, deletes the segments.

Every phase prints one JSON line (wall seconds, bytes, DispatchStats,
programs compiled and the seconds of each that took over half a second,
persistent-cache hits). The LAST line is `{"ok": true, "device": {...}}` and
nothing else. Any failing phase is an uncaught exception: no phase is wrapped
so that the script still ends with `ok`. It refuses to start when JAX finds
no TPU, or when a kernel switch is set in the environment, so what it runs is
what a deployment runs.

`--chips 4` runs only the path that exists across chips: the same seeded
64 MiB windows through a backend with `mesh.devices = 4` and one with
`mesh.devices = 1`, byte-identical, and prints where the rows and the GCM
constants live.

    python chip_smoke.py [--seed N] [--chips 4]
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import http.client
import json
import os
import pathlib
import shutil
import subprocess
import sys
import tempfile
import time

REPO_ROOT = pathlib.Path(__file__).resolve().parent

#: Kernel-path switches a deployment never sets (ROADMAP C3 removes them).
KERNEL_SWITCHES = (
    "TIEREDSTORAGE_TPU_PALLAS",
    "TIEREDSTORAGE_TPU_PALLAS_GHASH",
    "TIEREDSTORAGE_TPU_PALLAS_GHASH_TREE",
    "TSTPU_AES_SCAN",
    "TSTPU_AES_R",
)

#: Both Pallas kernels (AES circuit, GHASH tree) inside one window program.
EXPECTED_KERNEL_CALLS = 2

@dataclasses.dataclass(frozen=True)
class Sizes:
    """The deployment's widths. Tests shrink them; the script never does."""

    chunk_bytes: int = 4 << 20                 # upstream README:51-52
    segment_bytes: int = (1 << 30) - 300_000   # log.segment.bytes, unaligned
    device_cache_bytes: int = 4 << 30          # hot tier at a broker's size
    window_chunks: int = 16                    # 64 MiB windows (--chips 4)

    @property
    def n_chunks(self) -> int:
        return -(-self.segment_bytes // self.chunk_bytes)


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def refuse_kernel_switches() -> None:
    present = [name for name in KERNEL_SWITCHES if name in os.environ]
    if present:
        raise SystemExit(
            f"chip_smoke: unset {', '.join(present)} — the smoke runs the "
            "kernel paths a deployment runs, not a forced one"
        )


def require_tpu(chips: int | None = None) -> dict:
    """The device as JAX reports it; exits before any work off the TPU."""
    import jax

    devices = jax.devices()
    first = devices[0]
    if first.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (platform={first.platform!r}); "
            "this script runs on the chip only"
        )
    if chips is not None and len(devices) != chips:
        raise SystemExit(
            f"chip_smoke: --chips {chips} needs {chips} devices, "
            f"JAX reports {len(devices)}"
        )
    return {
        "platform": first.platform,
        "kind": first.device_kind,
        "count": len(devices),
    }


class CompileLog:
    """Counts what JAX compiles, per phase, from `jax.monitoring` events."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self) -> None:
        self.compiles: list[tuple[str, float]] = []
        self.cache_hits = 0
        self.cache_misses = 0

    def _on_duration(self, event: str, seconds: float, **kwargs) -> None:
        if event == self._COMPILE:
            self.compiles.append((str(kwargs.get("fun_name", "?")), seconds))

    def _on_event(self, event: str, **kwargs) -> None:
        if event == self._HIT:
            self.cache_hits += 1
        elif event == self._MISS:
            self.cache_misses += 1

    def __enter__(self) -> "CompileLog":
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)

    @contextlib.contextmanager
    def phase(self, name: str, backend=None):
        """Time one phase and print its line when it ends without raising.
        `backend` (a TpuTransformBackend) contributes its DispatchStats for
        this phase alone."""
        mark, hits, misses = len(self.compiles), self.cache_hits, self.cache_misses
        if backend is not None:
            backend.reset_dispatch_stats()
        fields: dict = {}
        start = time.perf_counter()
        yield fields
        wall = time.perf_counter() - start
        compiled = self.compiles[mark:]
        record = {
            "phase": name,
            "wall_s": round(wall, 3),
            **fields,
            "programs_compiled": len(compiled),
            "compile_s_total": round(sum(s for _, s in compiled), 3),
            "compile_s_each": [
                [fun, round(s, 2)] for fun, s in compiled if s >= 0.5
            ],
            "cache_hits": self.cache_hits - hits,
            "cache_misses": self.cache_misses - misses,
        }
        if backend is not None:
            record["dispatch"] = backend.dispatch_stats.as_dict()
        emit(record)


# ------------------------------------------------------------------ the data
def make_segment(seed: int, n_bytes: int) -> bytes:
    """Semi-compressible bytes shaped like Kafka log batches:
    incompressible payload interleaved with repetitive record scaffolding,
    made in bulk from `seed`."""
    import numpy as np

    rng = np.random.default_rng(seed)
    pattern = np.frombuffer(
        (b"offset=%019d key=user-%06d value=" % (0, 0)) * 64, dtype=np.uint8
    )
    out = np.empty(n_bytes, dtype=np.uint8)
    out[0::2] = rng.integers(0, 256, (n_bytes + 1) // 2, dtype=np.uint8)
    out[1::2] = np.resize(pattern, n_bytes // 2)
    return out.tobytes()


def make_indexes(seed: int, segment_bytes: int) -> dict:
    """The index sections `/v1/copy` requires, at the sizes a segment of
    this length has with Kafka's default `index.interval.bytes` = 4096:
    8 B per offset-index entry, 12 B per time-index entry."""
    import numpy as np

    rng = np.random.default_rng([seed, 1])
    entries = max(1, segment_bytes // 4096)
    return {
        "offset_index": rng.bytes(8 * entries),
        "time_index": rng.bytes(12 * entries),
        "producer_snapshot": rng.bytes(96),
        "transaction_index": None,
        "leader_epoch_index": b"0\n1\n0 0\n",
    }


def segment_metadata(seed: int, ordinal: int, segment_bytes: int):
    import numpy as np

    from tieredstorage_tpu.metadata import (
        KafkaUuid,
        RemoteLogSegmentId,
        RemoteLogSegmentMetadata,
        TopicIdPartition,
        TopicPartition,
    )

    rng = np.random.default_rng([seed, 2, ordinal])
    tip = TopicIdPartition(
        KafkaUuid(np.random.default_rng([seed, 2]).bytes(16)),
        TopicPartition("chip-smoke", 0),
    )
    return RemoteLogSegmentMetadata(
        RemoteLogSegmentId(tip, KafkaUuid(rng.bytes(16))),
        start_offset=ordinal * 1_000_000,
        end_offset=(ordinal + 1) * 1_000_000 - 1,
        segment_leader_epochs={0: ordinal * 1_000_000},
        segment_size_in_bytes=segment_bytes,
    )


# ------------------------------------------------------------ the deployment
class Deployment:
    """An RSM behind its HTTP gateway, in this process, and a shim-wire
    client for the gateway's routes."""

    def __init__(self, configs: dict) -> None:
        from tieredstorage_tpu.rsm import RemoteStorageManager
        from tieredstorage_tpu.sidecar.http_gateway import SidecarHttpGateway

        self.rsm = RemoteStorageManager()
        self.rsm.configure(configs)
        self.gateway = SidecarHttpGateway(self.rsm, port=0).start()

    def close(self) -> None:
        self.gateway.stop()
        self.rsm.close()

    def _post(self, path: str, parts: list) -> bytes:
        conn = http.client.HTTPConnection(
            "127.0.0.1", self.gateway.port, timeout=1100
        )
        try:
            conn.request(
                "POST", path, body=iter(parts),
                headers={"Content-Length": str(sum(len(p) for p in parts))},
            )
            response = conn.getresponse()
            body = response.read()
        finally:
            conn.close()
        if response.status not in (200, 204):
            raise RuntimeError(
                f"{path} answered {response.status}: {body[:400]!r}"
            )
        return body

    def copy(self, md, segment: bytes, indexes: dict) -> None:
        import struct

        from tieredstorage_tpu.sidecar import shimwire

        # shimwire.encode_sections' framing, as parts: the 1 GiB section is
        # sent as a view instead of being copied into one body.
        parts = [shimwire.encode_metadata(md)]
        sections = {"log_segment": segment, **indexes}
        for name in shimwire.COPY_SECTIONS:
            blob = sections[name]
            if blob is None:
                parts.append(b"\x00")
            else:
                parts += [struct.pack(">BQ", 1, len(blob)), memoryview(blob)]
        self._post("/v1/copy", parts)

    def fetch(self, md, start: int, end: int | None) -> bytes:
        from tieredstorage_tpu.sidecar import shimwire

        return self._post(
            "/v1/fetch",
            [shimwire.encode_metadata(md), shimwire.encode_fetch_tail(start, end)],
        )

    def fetch_index(self, md, name: str) -> bytes:
        from tieredstorage_tpu.sidecar import shimwire

        return self._post(
            "/v1/fetch-index",
            [shimwire.encode_metadata(md), shimwire.encode_index_type(name)],
        )

    def delete(self, md) -> None:
        from tieredstorage_tpu.sidecar import shimwire

        self._post("/v1/delete", [shimwire.encode_metadata(md)])


def base_configs(root: pathlib.Path, chunk_bytes: int) -> dict:
    """What both the deployment and the plain reference share: one store,
    one key pair, the chunk width. The reference keeps every other default,
    `CpuTransformBackend` among them."""
    from tieredstorage_tpu.security.rsa import generate_key_pair_pem_files

    (root / "remote").mkdir()
    public, private = generate_key_pair_pem_files(root, prefix="smoke")
    return {
        "storage.backend.class":
            "tieredstorage_tpu.storage.filesystem.FileSystemStorage",
        "storage.root": str(root / "remote"),
        "chunk.size": chunk_bytes,
        "encryption.enabled": True,
        "encryption.key.pair.id": "smoke",
        "encryption.key.pairs": "smoke",
        "encryption.key.pairs.smoke.public.key.file": str(public),
        "encryption.key.pairs.smoke.private.key.file": str(private),
    }


def tpu_configs(base: dict, sizes: Sizes, *, compression: bool) -> dict:
    configs = {
        **base,
        "transform.backend.class":
            "tieredstorage_tpu.transform.tpu.TpuTransformBackend",
        "cache.device.bytes": sizes.device_cache_bytes,
        "compression.enabled": compression,
        # An index is decrypted under the index cache's get timeout, 10 s by
        # default; the first decrypt of a new index size compiles for longer
        # than that on a cold chip.
        "fetch.indexes.cache.get.timeout.ms": 600_000,
    }
    if compression:
        configs["compression.codec"] = "zstd"
    return configs


def expect_equal(what: str, got: bytes, source: bytes, start: int, end: int) -> None:
    if memoryview(source)[start : end + 1] != got:
        raise AssertionError(
            f"{what}: {len(got)} bytes differ from source[{start}:{end + 1}]"
        )


def device_memory_stats() -> dict:
    import jax

    return jax.local_devices()[0].memory_stats() or {}


def device_peak_bytes() -> int | None:
    return device_memory_stats().get("peak_bytes_in_use")


# ---------------------------------------------------------------- the phases
def phase_gates(log: CompileLog) -> None:
    """Consult the three kernel gates eagerly, so each preflight runs (and
    raises, on a TPU, if its kernel does not) before any window is traced."""
    from tieredstorage_tpu.ops import aes_bitsliced, ghash_pallas

    with log.phase("gates") as out:
        for name, gate in (
            ("pallas_aes", aes_bitsliced.pallas_aes_available),
            ("pallas_ghash_level1", ghash_pallas.pallas_ghash_available),
            ("pallas_ghash_tree", ghash_pallas.pallas_ghash_tree_available),
        ):
            start = time.perf_counter()
            out[name] = bool(gate())
            out[f"{name}_s"] = round(time.perf_counter() - start, 3)


def build_native_fresh() -> dict:
    """Build native/libtransform_host.so from native/transform_host.cpp, so
    no library left in the working tree is loaded in its place; then say
    which zstd implementation the backend will run."""
    from tieredstorage_tpu import native
    from tieredstorage_tpu.transform.tpu import TpuTransformBackend

    native_dir = REPO_ROOT / "native"
    (native_dir / "libtransform_host.so").unlink(missing_ok=True)
    try:
        make_rc = subprocess.run(
            ["make", "-s", "-C", str(native_dir)], capture_output=True
        ).returncode
    except OSError as exc:
        make_rc = f"{type(exc).__name__}: {exc}"
    return {
        "phase": "native_build",
        "make_rc": make_rc,
        "zstd_engine": TpuTransformBackend.zstd_engine(),
        "load_error": native.load_error(),
    }


def phase_window_program(log: CompileLog, backend, sizes: Sizes,
                         expect_kernels: int) -> None:
    """Compile the fixed-shape window program the copy just ran, ahead of
    time, and read its text: how many Pallas kernels it holds, and what the
    compiler says it needs."""
    import jax
    import jax.numpy as jnp

    from tieredstorage_tpu.ops import gcm

    ctx = gcm.make_context(bytes(32), b"", sizes.chunk_bytes)
    rows = min(
        sizes.n_chunks, max(1, backend.preferred_batch_bytes // sizes.chunk_bytes)
    )

    def shape(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    with log.phase("window_program") as out:
        compiled = gcm._packed_jit(False, True, backend.mesh_plan().mesh).lower(
            shape(ctx.round_keys), None,
            jax.ShapeDtypeStruct((rows, sizes.chunk_bytes + 16), jnp.uint8),
            tuple(shape(m) for m in ctx.agg_mats), shape(ctx.final_mat),
            shape(ctx.const_bits), shape(ctx.step_mat),
            chunk_bytes=ctx.chunk_bytes, n_blocks=ctx.n_blocks, decrypt=False,
        ).compile()
        kernels = compiled.as_text().count("tpu_custom_call")
        memory = compiled.memory_analysis()
        out.update(
            rows=rows,
            row_bytes=sizes.chunk_bytes + 16,
            tpu_custom_calls=kernels,
            temp_bytes=memory.temp_size_in_bytes,
            argument_bytes=memory.argument_size_in_bytes,
            output_bytes=memory.output_size_in_bytes,
            alias_bytes=memory.alias_size_in_bytes,
        )
    if kernels != expect_kernels:
        raise AssertionError(
            f"window program holds {kernels} tpu_custom_call(s), "
            f"expected {expect_kernels}"
        )


def tpu_backend(deployment: Deployment):
    """The deployment's transform backend if it keeps DispatchStats (the
    plain reference's `CpuTransformBackend` keeps none)."""
    backend = deployment.rsm.transform_backend
    return backend if hasattr(backend, "dispatch_stats") else None


def phase_copy(log: CompileLog, name: str, deployment: Deployment, md,
               segment: bytes, indexes: dict) -> None:
    with log.phase(name, tpu_backend(deployment)) as out:
        deployment.copy(md, segment, indexes)
        out["bytes"] = len(segment)
        out["peak_bytes_in_use"] = device_peak_bytes()


def phase_fetch(log: CompileLog, name: str, deployment: Deployment, md,
                source: bytes, start: int, end: int | None) -> None:
    """One ranged fetch through the gateway, byte-compared with the source."""
    last = len(source) - 1 if end is None else end
    with log.phase(name, tpu_backend(deployment)) as out:
        got = deployment.fetch(md, start, end)
        expect_equal(name, got, source, start, last)
        out.update(start=start, end=last, bytes=len(got))


def ranged_fetches(sizes: Sizes) -> list[tuple[str, int, int]]:
    """One aligned chunk, an unaligned range across a chunk boundary, the
    ragged last chunk."""
    chunk = sizes.chunk_bytes
    aligned = min(3, sizes.n_chunks - 2)
    boundary = min(5, sizes.n_chunks - 1) * chunk
    reach = min(1000, chunk // 4)
    return [
        ("aligned_chunk", aligned * chunk, (aligned + 1) * chunk - 1),
        ("across_boundary", boundary - reach, boundary + 2 * reach),
        ("ragged_last_chunk", (sizes.n_chunks - 1) * chunk, sizes.segment_bytes - 1),
    ]


def phase_hot_tier(log: CompileLog, deployment: Deployment, md, source: bytes,
                   sizes: Sizes) -> None:
    """Repeat one aligned-chunk range until the device hot tier serves it,
    then hold it to what tools/hot_demo.py asserts on the CPU: no new GCM
    dispatch on the hit, the retained device buffer still live, identical
    bytes from the pinned mirror and from the device rows."""
    import numpy as np

    from tieredstorage_tpu.object_key import ObjectKeyFactory, Suffix
    from tieredstorage_tpu.ops import gcm

    hot = deployment.rsm.device_hot_cache
    if hot is None:
        raise AssertionError("cache.device.bytes did not arm the hot tier")
    chunk_id = min(3, sizes.n_chunks - 2)
    start = chunk_id * sizes.chunk_bytes
    end = start + sizes.chunk_bytes - 1
    key = ObjectKeyFactory(None).key(md, Suffix.LOG)
    with log.phase("hot_tier", tpu_backend(deployment)) as out:
        for attempt in range(1, 6):
            hits, dispatches = hot.hits, gcm.device_dispatches()
            got = deployment.fetch(md, start, end)
            expect_equal("hot_tier", got, source, start, end)
            if hot.hits > hits:
                break
        else:
            raise AssertionError("the hot tier never served the repeated range")
        new_dispatches = gcm.device_dispatches() - dispatches
        window = hot.window(key, chunk_id)
        retained = window is not None and window.device is not None
        rows = hot.device_rows(key, [chunk_id])
        out.update(
            fetches_until_hit=attempt,
            dispatches_on_hit=new_dispatches,
            device_buffer_retained=retained,
            device_buffer_deleted=bool(retained and window.device.is_deleted()),
            resident_device_bytes=hot.resident_device_bytes,
            budget_bytes=hot.budget_bytes,
        )
        if new_dispatches != 0:
            raise AssertionError(f"hot hit cost {new_dispatches} GCM dispatches")
        if not retained or window.device.is_deleted():
            raise AssertionError("hot window holds no live device buffer")
        if rows is None or (
            np.asarray(rows[0])[: sizes.chunk_bytes].tobytes() != got
        ):
            raise AssertionError("retained device rows differ from the source")


def serve_plain(log: CompileLog, base: dict, reference: Deployment, seed: int,
                sizes: Sizes, segment: bytes, indexes: dict,
                expect_kernels: int) -> None:
    """BASELINE config 1's shape with AES on: compression off — fifteen full
    64 MiB fixed-shape windows and a ragged one at the real widths."""
    deployment = Deployment(tpu_configs(base, sizes, compression=False))
    try:
        md = segment_metadata(seed, 0, len(segment))
        phase_copy(log, "copy_plain", deployment, md, segment, indexes)
        phase_window_program(log, tpu_backend(deployment), sizes, expect_kernels)
        phase_fetch(log, "reference_reads_tpu_upload", reference, md, segment, 0, None)
        phase_fetch(log, "fetch_whole_segment", deployment, md, segment, 0, None)
        for name, start, end in ranged_fetches(sizes):
            phase_fetch(log, f"fetch_{name}", deployment, md, segment, start, end)
        phase_hot_tier(log, deployment, md, segment, sizes)

        # The other direction: the plain reference uploads, the TPU reads.
        md_ref = segment_metadata(seed, 1, len(segment))
        with log.phase("reference_copy") as out:
            reference.copy(md_ref, segment, indexes)
            out["bytes"] = len(segment)
        phase_fetch(log, "tpu_reads_reference_upload", deployment, md_ref,
                    segment, 0, None)

        with log.phase("fetch_index", tpu_backend(deployment)) as out:
            got = deployment.fetch_index(md, "OFFSET")
            if got != indexes["offset_index"]:
                raise AssertionError("offset index differs from what was copied")
            out["bytes"] = len(got)
        with log.phase("delete") as out:
            deployment.delete(md)
            deployment.delete(md_ref)
            left = [p for p in pathlib.Path(base["storage.root"]).rglob("*")
                    if p.is_file()]
            out["objects_left"] = len(left)
            if left:
                raise AssertionError(f"delete left {left[:3]} behind")
    finally:
        deployment.close()


def serve_zstd(log: CompileLog, base: dict, reference: Deployment, seed: int,
               sizes: Sizes, segment: bytes, indexes: dict) -> None:
    """BASELINE config 2: zstd then AES — every window is varlen, on the
    bucket ladder. Each fetched chunk is its own fixed-shape decrypt program
    (the compressed size is the shape), so only the three ranges are fetched
    through the TPU; the whole segment is read back by the reference."""
    deployment = Deployment(tpu_configs(base, sizes, compression=True))
    try:
        md = segment_metadata(seed, 2, len(segment))
        phase_copy(log, "copy_zstd", deployment, md, segment, indexes)
        phase_fetch(log, "reference_reads_tpu_zstd_upload", reference, md,
                    segment, 0, None)
        for name, start, end in ranged_fetches(sizes):
            phase_fetch(log, f"fetch_zstd_{name}", deployment, md, segment,
                        start, end)
        with log.phase("delete_zstd"):
            deployment.delete(md)
    finally:
        deployment.close()


def run_one_chip(seed: int, sizes: Sizes, log: CompileLog, *,
                 expect_kernels: int = EXPECTED_KERNEL_CALLS) -> None:
    root = pathlib.Path(tempfile.mkdtemp(prefix="chip-smoke-"))
    try:
        phase_gates(log)
        base = base_configs(root, sizes.chunk_bytes)
        reference = Deployment(base)
        try:
            with log.phase("make_data") as out:
                segment = make_segment(seed, sizes.segment_bytes)
                indexes = make_indexes(seed, sizes.segment_bytes)
                out["bytes"] = len(segment)
            serve_plain(log, base, reference, seed, sizes, segment, indexes,
                        expect_kernels)
            serve_zstd(log, base, reference, seed, sizes, segment, indexes)
        finally:
            reference.close()
    finally:
        shutil.rmtree(root, ignore_errors=True)


# ------------------------------------------------------------- across chips
def placement(array) -> dict:
    """Where a device array lives: one entry per addressable shard."""
    return {
        "shape": list(array.shape),
        "sharding": str(getattr(array.sharding, "spec", array.sharding)),
        "fully_replicated": bool(array.is_fully_replicated),
        "shards": [
            [shard.device.id, list(shard.data.shape)]
            for shard in array.addressable_shards
        ],
    }


def run_across_chips(seed: int, sizes: Sizes, log: CompileLog, *,
                     mesh_devices: int = 4) -> None:
    """The mesh path and what it is compared with, and no other phase: the
    same seeded 64 MiB windows — fixed and varlen, encrypt and decrypt —
    through a backend sharding rows over `mesh_devices` chips and through a
    single-device one; byte-identical, one logical dispatch per window,
    every staged buffer donated."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tieredstorage_tpu.ops import gcm
    from tieredstorage_tpu.security.aes import DataKeyAndAAD
    from tieredstorage_tpu.transform.api import DetransformOptions, TransformOptions
    from tieredstorage_tpu.transform.cpu import CpuTransformBackend
    from tieredstorage_tpu.transform.tpu import TpuTransformBackend

    rng = np.random.default_rng([seed, 3])
    rows, chunk = sizes.window_chunks, sizes.chunk_bytes
    data = make_segment(seed, rows * chunk)
    fixed = [data[i * chunk : (i + 1) * chunk] for i in range(rows)]
    # Distinct lengths inside the top bucket of the varlen ladder.
    lengths = chunk - 1 - rng.choice(chunk // 16, size=rows, replace=False)
    varlen = [c[: int(n)] for c, n in zip(fixed, lengths)]
    key = DataKeyAndAAD(data_key=rng.bytes(32), aad=rng.bytes(32))
    ivs = [rng.bytes(12) for _ in range(rows)]
    encrypt = TransformOptions(encryption=key, ivs=ivs)
    decrypt = DetransformOptions(encryption=key)

    sharded, single = TpuTransformBackend(), TpuTransformBackend()
    sharded.configure({"mesh.devices": mesh_devices})
    single.configure({"mesh.devices": 1})
    outputs: list = []
    sharded.on_decrypt_window = lambda out, *_: outputs.append(out)

    def window(name: str, backend, run) -> list[bytes]:
        """One window through one backend: a phase line, and the window
        contract — one logical dispatch, its staged buffer donated, rows
        spread evenly over the backend's devices."""
        with log.phase(name, backend) as out:
            result = run()
            out["bytes"] = sum(len(c) for c in result)
        stats = backend.dispatch_stats
        devices = mesh_devices if backend is sharded else 1
        if not (stats.windows == stats.dispatches == stats.donated_buffers == 1
                and stats.mesh_size == devices
                and stats.rows_per_device * devices == rows):
            raise AssertionError(f"{name}: window contract broken: {stats}")
        return result

    try:
        for shape, chunks in (("fixed", fixed), ("varlen", varlen)):
            wire = window(f"mesh_{shape}_encrypt", sharded,
                          lambda: sharded.transform(chunks, encrypt))
            if wire != window(f"single_{shape}_encrypt", single,
                              lambda: single.transform(chunks, encrypt)):
                raise AssertionError(f"{shape}: sharded wire bytes differ")
            if wire != CpuTransformBackend().transform(chunks, encrypt):
                raise AssertionError(f"{shape}: wire bytes differ from AESGCM")
            for label, backend in (("mesh", sharded), ("single", single)):
                plain = window(f"{label}_{shape}_decrypt", backend,
                               lambda: backend.detransform(wire, decrypt))
                if plain != chunks:
                    raise AssertionError(f"{label} {shape}: decrypt differs")

        # Where things live. A bare jnp.asarray lands on one device,
        # uncommitted; the window program's constants must not.
        plan = sharded.mesh_plan()
        ctx = gcm.make_context(key.data_key, key.aad, chunk)
        bare = jnp.asarray(ctx.final_mat)
        consts = jax.tree_util.tree_leaves(
            (gcm._device_consts(ctx, plan.mesh), gcm._device_step_mat(ctx, plan.mesh))
        )
        emit({
            "phase": "placement",
            "staged_window": placement(
                plan.shard(np.zeros((rows, chunk + 16), np.uint8))
            ),
            "decrypt_output": placement(outputs[0]),
            "bare_asarray": {
                "devices": sorted(d.id for d in bare.devices()),
                "committed": bool(bare.committed),
            },
            "gcm_constants": [placement(c) for c in consts],
            "gcm_constants_bytes": sum(int(c.nbytes) for c in consts),
        })
        for c in consts:
            if len(c.addressable_shards) != mesh_devices or not c.is_fully_replicated:
                raise AssertionError("a GCM constant is not replicated over the mesh")
    finally:
        sharded.close()
        single.close()


def cache_entries(cache_dir: str) -> int:
    path = pathlib.Path(cache_dir)
    return sum(1 for p in path.iterdir()) if path.is_dir() else 0


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0,
                        help="seed of the segment bytes, indexes and ids")
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: run only the path across four chips")
    args = parser.parse_args(argv)

    refuse_kernel_switches()
    device = require_tpu(4 if args.chips == 4 else None)

    from tieredstorage_tpu.utils.platforms import enable_compile_cache

    cache_dir = enable_compile_cache()
    sizes = Sizes()
    emit({
        "phase": "start", "seed": args.seed, "device": device,
        "sizes": dataclasses.asdict(sizes), "compile_cache_dir": cache_dir,
        "compile_cache_entries": cache_entries(cache_dir),
    })
    with CompileLog() as log:
        if args.chips == 4:
            run_across_chips(args.seed, sizes, log)
        else:
            emit(build_native_fresh())
            run_one_chip(args.seed, sizes, log)
        emit({
            "phase": "end",
            "programs_compiled": len(log.compiles),
            "compile_s_total": round(sum(s for _, s in log.compiles), 3),
            "cache_hits": log.cache_hits, "cache_misses": log.cache_misses,
            "compile_cache_entries": cache_entries(cache_dir),
            "memory_stats": device_memory_stats(),
        })
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
