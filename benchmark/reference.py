"""The plain reference: the stored wire format, read and written with
`cryptography`'s AESGCM and `zstandard` and nothing of the program.

One segment is three objects under the store's root, as upstream's
`tiered-storage-for-apache-kafka` lays them out:

    <topic>-<topicId>/<partition>/<startOffset:020d>-<segmentId>.log
        chunks back to back, each IV(12) || ciphertext || tag(16); the
        plaintext of a chunk is zstd of `chunk.size` bytes where
        `compression` is true
    ....indexes        each index one such chunk, encrypt-only, concatenated
    ....rsm-manifest   JSON: chunkIndex, segmentIndexes, compression,
                       encryption {dataKey "<keyId>:<b64 RSA-OAEP>", aad b64}

The data key is wrapped with RSA/OAEP(SHA3-512, MGF1-SHA3-512), which OpenSSL
does not offer, so the padding is written out here from RFC 8017 section 7.1.
"""

from __future__ import annotations

import base64
import dataclasses
import hashlib
import json
import os
import pathlib
import struct

from cryptography.hazmat.primitives import serialization
from cryptography.hazmat.primitives.asymmetric import rsa
from cryptography.hazmat.primitives.ciphers.aead import AESGCM

IV, TAG = 12, 16
INDEX_NAMES = {  # manifest key -> the copy request's section
    "offset": "offset_index",
    "timestamp": "time_index",
    "producerSnapshot": "producer_snapshot",
    "leaderEpoch": "leader_epoch_index",
    "transaction": "transaction_index",
}
_HASH = hashlib.sha3_512
_HLEN = _HASH().digest_size


def uuid_text(raw: bytes) -> str:
    return base64.urlsafe_b64encode(raw).decode("ascii").rstrip("=")


@dataclasses.dataclass(frozen=True)
class SegmentName:
    """What names a segment's objects in the store."""

    topic: str
    topic_id: bytes
    partition: int
    start_offset: int
    segment_id: bytes

    @classmethod
    def seeded(cls, seed: int, ordinal: int) -> "SegmentName":
        """The `ordinal`-th segment of the run's one partition."""
        import numpy as np

        return cls(
            topic="benchmark",
            topic_id=np.random.default_rng([seed, 2]).bytes(16),
            partition=0,
            start_offset=ordinal * 1_000_000,
            segment_id=np.random.default_rng([seed, 2, ordinal]).bytes(16),
        )

    def path(self, root: pathlib.Path, suffix: str) -> pathlib.Path:
        return (
            root / f"{self.topic}-{uuid_text(self.topic_id)}" / str(self.partition)
            / f"{self.start_offset:020d}-{uuid_text(self.segment_id)}.{suffix}"
        )


# ------------------------------------------------------------------ key pair
def new_key_pair(directory: pathlib.Path, prefix: str):
    """A fresh RSA pair as PEM files; returns (private key, public path,
    private path)."""
    key = rsa.generate_private_key(public_exponent=65537, key_size=2048)
    public = directory / f"{prefix}_public.pem"
    private = directory / f"{prefix}_private.pem"
    public.write_bytes(key.public_key().public_bytes(
        serialization.Encoding.PEM, serialization.PublicFormat.SubjectPublicKeyInfo
    ))
    private.write_bytes(key.private_bytes(
        serialization.Encoding.PEM, serialization.PrivateFormat.PKCS8,
        serialization.NoEncryption(),
    ))
    return key, public, private


def _mgf1(seed: bytes, length: int) -> bytes:
    out = b"".join(
        _HASH(seed + struct.pack(">I", counter)).digest()
        for counter in range(-(-length // _HLEN))
    )
    return out[:length]


def _xor(a: bytes, b: bytes) -> bytes:
    return (int.from_bytes(a, "big") ^ int.from_bytes(b, "big")).to_bytes(len(a), "big")


def wrap_key(key: rsa.RSAPrivateKey, data_key: bytes) -> bytes:
    numbers = key.public_key().public_numbers()
    k = (numbers.n.bit_length() + 7) // 8
    padding = b"\x00" * (k - len(data_key) - 2 * _HLEN - 2)
    block = _HASH(b"").digest() + padding + b"\x01" + data_key
    seed = os.urandom(_HLEN)
    masked_block = _xor(block, _mgf1(seed, len(block)))
    masked_seed = _xor(seed, _mgf1(masked_block, _HLEN))
    encoded = b"\x00" + masked_seed + masked_block
    return pow(int.from_bytes(encoded, "big"), numbers.e, numbers.n).to_bytes(k, "big")


def unwrap_key(key: rsa.RSAPrivateKey, wrapped: bytes) -> bytes:
    numbers = key.private_numbers()
    n = numbers.public_numbers.n
    k = (n.bit_length() + 7) // 8
    encoded = pow(int.from_bytes(wrapped, "big"), numbers.d, n).to_bytes(k, "big")
    masked_seed, masked_block = encoded[1 : 1 + _HLEN], encoded[1 + _HLEN :]
    seed = _xor(masked_seed, _mgf1(masked_block, _HLEN))
    block = _xor(masked_block, _mgf1(seed, len(masked_block)))
    split = block.find(b"\x01", _HLEN)
    if encoded[0] != 0 or block[:_HLEN] != _HASH(b"").digest() or split < 0:
        raise ValueError("the wrapped data key does not unpad")
    return block[split + 1 :]


# ------------------------------------------------------------ chunk indexes
def decode_chunk_sizes(data: bytes) -> list[int]:
    """upstream ChunkSizesBinaryCodec: [count:4][base:4][width:1]
    [(count-1) x width][last:4], big-endian, values stored less `base`."""
    (count,) = struct.unpack_from(">i", data, 0)
    if count == 0:
        return []
    if count == 1:
        return [struct.unpack_from(">i", data, 4)[0]]
    base, width = struct.unpack_from(">iB", data, 4)
    body = data[9 : 9 + (count - 1) * width]
    sizes = [
        base + int.from_bytes(body[i : i + width], "big")
        for i in range(0, len(body), width)
    ]
    return sizes + [struct.unpack_from(">i", data, 9 + len(body))[0]]


def transformed_sizes(chunk_index: dict) -> list[int]:
    if chunk_index["type"] == "variable":
        return decode_chunk_sizes(base64.b64decode(chunk_index["transformedChunks"]))
    full = chunk_index["originalFileSize"] // chunk_index["originalChunkSize"]
    sizes = [chunk_index["transformedChunkSize"]] * full
    if chunk_index["originalFileSize"] % chunk_index["originalChunkSize"]:
        sizes.append(chunk_index["finalTransformedChunkSize"])
    return sizes


# -------------------------------------------------------------------- reading
@dataclasses.dataclass
class StoredSegment:
    data_key: bytes
    segment: bytes
    indexes: dict  # section name -> bytes or None


def _open_chunk(cipher: AESGCM, aad: bytes, blob) -> bytes:
    blob = bytes(blob)
    return cipher.decrypt(blob[:IV], blob[IV:], aad)  # raises InvalidTag


def read_segment(root: pathlib.Path, name: SegmentName,
                 key: rsa.RSAPrivateKey) -> StoredSegment:
    """The source bytes and indexes of a stored segment, from its three
    objects alone. Raises on a missing object, a tag that does not verify or
    a length that does not add up."""
    import zstandard

    manifest = json.loads(name.path(root, "rsm-manifest").read_text())
    _, _, wrapped = manifest["encryption"]["dataKey"].partition(":")
    data_key = unwrap_key(key, base64.b64decode(wrapped))
    aad = base64.b64decode(manifest["encryption"]["aad"])
    cipher = AESGCM(data_key)
    chunk_index = manifest["chunkIndex"]

    log = memoryview(name.path(root, "log").read_bytes())
    sizes = transformed_sizes(chunk_index)
    if sum(sizes) != len(log):
        raise ValueError(f".log holds {len(log)} bytes, the index {sum(sizes)}")
    chunks, at = [], 0
    for size in sizes:
        plain = _open_chunk(cipher, aad, log[at : at + size])
        if manifest["compression"]:
            plain = zstandard.ZstdDecompressor().decompress(
                plain, max_output_size=chunk_index["originalChunkSize"]
            )
        chunks.append(plain)
        at += size
    segment = b"".join(chunks)
    if len(segment) != chunk_index["originalFileSize"]:
        raise ValueError("the chunks do not add up to originalFileSize")

    blob = memoryview(name.path(root, "indexes").read_bytes())
    indexes = {}
    for manifest_key, section in INDEX_NAMES.items():
        entry = manifest["segmentIndexes"].get(manifest_key)
        if entry is None:
            indexes[section] = None
        elif entry["size"] == 0:
            indexes[section] = b""
        else:
            indexes[section] = _open_chunk(
                cipher, aad, blob[entry["position"] : entry["position"] + entry["size"]]
            )
    return StoredSegment(data_key, segment, indexes)


# -------------------------------------------------------------------- writing
def write_segment(root: pathlib.Path, name: SegmentName, key: rsa.RSAPrivateKey,
                  key_id: str, segment: bytes, indexes: dict, chunk_bytes: int) -> None:
    """Store `segment` as an encrypt-only upload would: a fresh data key and
    AAD, a random IV per chunk, the manifest written last."""
    data_key, aad = os.urandom(32), os.urandom(32)
    cipher = AESGCM(data_key)

    def seal(plain) -> bytes:
        iv = os.urandom(IV)
        return iv + cipher.encrypt(iv, bytes(plain), aad)

    view = memoryview(segment)
    log_path = name.path(root, "log")
    log_path.parent.mkdir(parents=True, exist_ok=True)
    with open(log_path, "wb") as out:
        for at in range(0, len(view), chunk_bytes):
            out.write(seal(view[at : at + chunk_bytes]))

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

    ragged = len(segment) % chunk_bytes
    manifest = {
        "version": "1",
        "chunkIndex": {
            "type": "fixed",
            "originalChunkSize": chunk_bytes,
            "originalFileSize": len(segment),
            "transformedChunkSize": chunk_bytes + IV + TAG,
            "finalTransformedChunkSize": (ragged or chunk_bytes) + IV + TAG,
        },
        "segmentIndexes": entries,
        "compression": False,
        "encryption": {
            "dataKey": f"{key_id}:"
            + base64.b64encode(wrap_key(key, data_key)).decode("ascii"),
            "aad": base64.b64encode(aad).decode("ascii"),
        },
    }
    name.path(root, "rsm-manifest").write_text(json.dumps(manifest))
