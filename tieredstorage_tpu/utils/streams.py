"""Stream helpers: bounded reads, chunked copy, lazy concatenation (of
streams for an upload, of views for a fetch).

Host-side equivalents of the reference's commons-io BoundedInputStream usage
(core/.../fetch/FetchChunkEnumeration.java:100-131) and SequenceInputStream
composition (core/.../transform/DetransformFinisher.java:48-53).
"""

from __future__ import annotations

import io
import sys
from typing import BinaryIO, Callable, Iterator, Optional

_COPY_BUF = 1024 * 1024


class BoundedStream(io.RawIOBase):
    """Caps reads from an inner stream at `limit` bytes; closes inner on close."""

    def __init__(self, inner: BinaryIO, limit: int):
        self._inner = inner
        self._remaining = max(0, limit)

    def readable(self) -> bool:
        return True

    def read(self, size: int = -1) -> bytes:
        if self._remaining <= 0:
            return b""
        if size is None or size < 0 or size > self._remaining:
            size = self._remaining
        data = self._inner.read(size)
        self._remaining -= len(data)
        return data

    def readinto(self, b) -> int:
        data = self.read(len(b))
        b[: len(data)] = data
        return len(data)

    def close(self) -> None:
        try:
            self._inner.close()
        finally:
            super().close()


class LazyConcatStream(io.RawIOBase):
    """Concatenates streams produced on demand by an iterator of factories.

    The analogue of the reference's LazySequenceInputStream
    (core/.../fetch/FetchChunkEnumeration.java:160-175): the iterator is only
    advanced when more bytes are requested, and closing the stream early stops
    the iteration (the broker rarely drains a whole fetch).
    """

    def __init__(self, parts: Iterator[BinaryIO]):
        self._parts = parts
        self._current: Optional[BinaryIO] = None

    def readable(self) -> bool:
        return True

    def read(self, size: int = -1) -> bytes:
        if size == 0:
            return b""
        out = bytearray()
        while size < 0 or len(out) < size:
            if self._current is None:
                try:
                    self._current = next(self._parts)
                except StopIteration:
                    break
            want = -1 if size < 0 else size - len(out)
            data = self._current.read(want)
            if not data:
                self._current.close()
                self._current = None
                continue
            out += data
        return bytes(out)

    def readinto(self, b) -> int:
        data = self.read(len(b))
        b[: len(data)] = data
        return len(data)

    def close(self) -> None:
        try:
            if self._current is not None:
                self._current.close()
                self._current = None
            close_all = getattr(self._parts, "close", None)
            if close_all is not None:
                close_all()
        finally:
            super().close()


class ViewConcatStream(io.RawIOBase):
    """Concatenates buffers produced on demand by an iterator of views, and
    hands them on as views.

    The fetch path's `LazyConcatStream` (fetch/enumeration.py): the iterator
    is advanced only once every byte of the view before has been taken, and
    closing the stream early stops the iteration. `read_view` and
    `read_views` are the ways out that copy nothing: the gateway hands what
    they return to the socket. `read` and `readinto` are the `BinaryIO` of
    in-process callers, one copy each. The iterator bounds the stream: every
    view is served whole.
    """

    def __init__(self, parts: Iterator[memoryview]):
        self._parts = parts
        #: What is left of the view being served.
        self._current: Optional[memoryview] = None

    def readable(self) -> bool:
        return True

    def read_view(self, size: int) -> memoryview:
        """The next at most `size` bytes as a view of the buffer the iterator
        yielded them in (`.obj` is that buffer's owner, which the view keeps
        alive): never across two buffers, so fewer than `size` says nothing
        of the stream's end; empty only there."""
        while not self._current:
            try:
                self._current = next(self._parts)
            except StopIteration:
                self._current = None
                return memoryview(b"")
        view = self._current[:size]
        self._current = self._current[size:]
        return view

    def read_views(self, size: int) -> list[memoryview]:
        """The next `size` bytes (all that are left when negative) as the
        views they lie in, in order: fewer bytes only at the stream's end,
        none only there. A buffer that ends inside the range is followed by
        the next one's view, so a block for a gather write is whole."""
        left = sys.maxsize if size is None or size < 0 else size
        views = []
        while left and (view := self.read_view(left)):
            views.append(view)
            left -= len(view)
        return views

    def read(self, size: int = -1) -> bytes:
        return b"".join(self.read_views(size))

    def readinto(self, b) -> int:
        out = memoryview(b).cast("B")
        filled = 0
        for view in self.read_views(len(out)):
            out[filled : filled + len(view)] = view
            filled += len(view)
        return filled

    def close(self) -> None:
        try:
            self._current = None
            close_all = getattr(self._parts, "close", None)
            if close_all is not None:
                close_all()
        finally:
            super().close()


def copy_stream(src: BinaryIO, dst: BinaryIO, buf_size: int = _COPY_BUF) -> int:
    total = 0
    while True:
        data = src.read(buf_size)
        if not data:
            break
        dst.write(data)
        total += len(data)
    return total


def read_exactly(stream: BinaryIO, n: int) -> bytes:
    """Read exactly n bytes or raise EOFError (reference:
    BaseDetransformChunkEnumeration.fillChunkIfNeeded errors on short streams,
    core/.../transform/BaseDetransformChunkEnumeration.java:78-113)."""
    data = stream.read(n)
    if data is not None and len(data) >= n:
        # One read had it all (a file, a ranged GET's body): hand its bytes
        # on as they are. Gathering them below costs two more copies of a
        # 4 MiB chunk, both under the interpreter lock.
        return data if isinstance(data, bytes) else bytes(data)
    out = bytearray()
    while data:
        out += data
        if len(out) >= n:
            return bytes(out)
        data = stream.read(n - len(out))
    raise EOFError(f"Stream has fewer than expected bytes: wanted {n}, got {len(out)}")


class ClosableStreamHolder:
    """Collects opened streams and best-effort closes them all.

    Reference: core/.../ClosableInputStreamHolder.java:28-48 (prevents fd
    leaks during multi-stream index upload).
    """

    def __init__(self) -> None:
        self._streams: list[BinaryIO] = []

    def add(self, stream: BinaryIO) -> BinaryIO:
        self._streams.append(stream)
        return stream

    def __enter__(self) -> "ClosableStreamHolder":
        return self

    def __exit__(self, *exc) -> None:
        for s in self._streams:
            try:
                s.close()
            except Exception:
                pass
