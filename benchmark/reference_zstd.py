"""The plain reference's writer for a deployment that compresses: everything
of `reference` (the reader, the key pair, the names) and a `write_segment` of
the same signature that stores a segment as a zstd + AES-256-GCM upload would.

Each `chunk.size` slice is one zstd frame with its content size written (as
zstd-jni's `Zstd.compress` writes it, upstream CompressionChunkEnumeration),
sealed like `reference.write_segment` seals a plain slice: IV(12) ||
ciphertext || tag(16). The stored chunks then differ in size, so the manifest
carries a `variable` chunk index: the sizes in upstream's
`ChunkSizesBinaryCodec`, the inverse of `reference.decode_chunk_sizes`. The
indexes are encrypt-only, as upstream stores them. `reference.read_segment`
reads all of it back to the source bytes. Nothing of the program is imported.
"""

from __future__ import annotations

import base64
import json
import os
import pathlib
import struct

from reference import *  # noqa: F401,F403
from reference import IV, INDEX_NAMES, AESGCM, SegmentName, rsa, wrap_key

#: zstd-jni's default level, and the program's `compression.level` default.
ZSTD_LEVEL = 3


def encode_chunk_sizes(sizes: list[int]) -> bytes:
    """upstream ChunkSizesBinaryCodec: [count:4][base:4][width:1]
    [(count-1) x width][last:4], big-endian; `base` is the least of all but
    the last value, each of those stored less `base` in the fewest whole
    bytes that hold the largest."""
    if not sizes:
        return struct.pack(">i", 0)
    if len(sizes) == 1:
        return struct.pack(">ii", 1, sizes[0])
    body, last = sizes[:-1], sizes[-1]
    base = min(body)
    width = max(1, (max(body) - base).bit_length() + 7 >> 3)
    return (
        struct.pack(">iiB", len(sizes), base, width)
        + b"".join((size - base).to_bytes(width, "big") for size in body)
        + struct.pack(">i", last)
    )


def stored_sizes(root: pathlib.Path, name: SegmentName) -> list[int]:
    """The stored (transformed) size of each chunk of a segment, from its
    manifest."""
    manifest = json.loads(name.path(root, "rsm-manifest").read_text())
    return transformed_sizes(manifest["chunkIndex"])  # noqa: F405


def write_segment(root: pathlib.Path, name: SegmentName, key: rsa.RSAPrivateKey,
                  key_id: str, segment: bytes, indexes: dict, chunk_bytes: int) -> None:
    """Store `segment` as a compressing, encrypting upload would: a fresh data
    key and AAD, each chunk a zstd frame under a random IV, the manifest
    written last."""
    import zstandard

    data_key, aad = os.urandom(32), os.urandom(32)
    cipher = AESGCM(data_key)
    # One compressor per call: they are not shared between set-up's threads.
    compressor = zstandard.ZstdCompressor(level=ZSTD_LEVEL, write_content_size=True)

    def seal(plain) -> bytes:
        iv = os.urandom(IV)
        return iv + cipher.encrypt(iv, bytes(plain), aad)

    view = memoryview(segment)
    sizes = []
    log_path = name.path(root, "log")
    log_path.parent.mkdir(parents=True, exist_ok=True)
    with open(log_path, "wb") as out:
        for at in range(0, len(view), chunk_bytes):
            sealed = seal(compressor.compress(view[at : at + chunk_bytes]))
            sizes.append(len(sealed))
            out.write(sealed)

    entries, parts, position = {}, [], 0
    for manifest_key, section in INDEX_NAMES.items():
        blob = indexes.get(section)
        if blob is None:
            entries[manifest_key] = None
            continue
        sealed = seal(blob) if blob else b""
        entries[manifest_key] = {"position": position, "size": len(sealed)}
        parts.append(sealed)
        position += len(sealed)
    name.path(root, "indexes").write_bytes(b"".join(parts))

    manifest = {
        "version": "1",
        "chunkIndex": {
            "type": "variable",
            "originalChunkSize": chunk_bytes,
            "originalFileSize": len(segment),
            "transformedChunks": base64.b64encode(encode_chunk_sizes(sizes)).decode("ascii"),
        },
        "segmentIndexes": entries,
        "compression": True,
        "encryption": {
            "dataKey": f"{key_id}:"
            + base64.b64encode(wrap_key(key, data_key)).decode("ascii"),
            "aad": base64.b64encode(aad).decode("ascii"),
        },
    }
    name.path(root, "rsm-manifest").write_text(json.dumps(manifest))
