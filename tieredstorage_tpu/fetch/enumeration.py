"""Ranged fetch over chunks: map an original-byte range to chunk streams.

Reference: core/.../fetch/FetchChunkEnumeration.java — chunk id window from
the chunk index (ctor :54-70), skip into the first chunk and cap the last
(:100-131), lazy stream so early close stops fetching (:160-175; the broker
rarely drains a whole fetch).

Where the reference wraps each chunk in a stream, this one yields views: the
plaintext a tier returned (a cache-held or freshly decrypted `bytes`, a slice
of a hot-tier mirror) is never sliced into a copy or wrapped in a `BytesIO`,
so the gateway can hand the socket a `memoryview` of that same object.
"""

from __future__ import annotations

from typing import BinaryIO, Iterator

from tieredstorage_tpu.errors import RemoteResourceNotFoundException
from tieredstorage_tpu.fetch.chunk_manager import ChunkManager
from tieredstorage_tpu.manifest.segment_manifest import SegmentManifestV1
from tieredstorage_tpu.storage.core import BytesRange, KeyNotFoundException, ObjectKey
from tieredstorage_tpu.utils.streams import ViewConcatStream


class FetchChunkEnumeration:
    def __init__(
        self,
        chunk_manager: ChunkManager,
        objects_key: ObjectKey,
        manifest: SegmentManifestV1,
        byte_range: BytesRange,
    ):
        self._chunk_manager = chunk_manager
        self._key = objects_key
        self._manifest = manifest
        index = manifest.chunk_index

        first_chunk = index.find_chunk_for_original_offset(byte_range.from_position)
        if first_chunk is None:
            raise ValueError(
                f"Invalid start position {byte_range.from_position} "
                f"in segment path {objects_key}"
            )
        self._first_chunk_id = first_chunk.id
        last_offset = min(byte_range.to_position, index.original_file_size - 1)
        self._last_chunk_id = index.find_chunk_for_original_offset(last_offset).id
        self._skip_in_first = byte_range.from_position - first_chunk.original_position
        self._total = min(byte_range.size, index.original_file_size - byte_range.from_position)

    def _parts(self) -> Iterator[memoryview]:
        """The range's bytes, chunk by chunk, each a view of the object the
        chunk manager returned: a chunk is asked for only once the consumer
        has drained the one before, and closing the generator ends it."""
        remaining = self._total
        try:
            for chunk_id in range(self._first_chunk_id, self._last_chunk_id + 1):
                data = self._chunk_manager.get_chunks(self._key, self._manifest, [chunk_id])[0]
                skip = self._skip_in_first if chunk_id == self._first_chunk_id else 0
                view = memoryview(data)[skip:][:remaining]
                remaining -= len(view)
                yield view
        except KeyNotFoundException as e:
            raise RemoteResourceNotFoundException(str(e)) from e

    def to_stream(self) -> BinaryIO:
        """A lazy `BinaryIO` over the range that also hands its bytes out as
        views (`read_view`, `read_views`)."""
        return ViewConcatStream(self._parts())
