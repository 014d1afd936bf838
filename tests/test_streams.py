"""`utils.streams.read_exactly`: the chunk manager's and the scrubber's read
of a stored chunk. One read that has it all is handed on with no copy; a
stream that dribbles is gathered; a short one raises."""

from __future__ import annotations

import io

import pytest

from tieredstorage_tpu.utils.streams import BoundedStream, read_exactly


class Dribble(io.RawIOBase):
    """Hands out at most `step` bytes a read, of the type `as_type` makes."""

    def __init__(self, data: bytes, step: int, as_type=bytes) -> None:
        self._data, self._step, self._as_type, self.reads = data, step, as_type, 0

    def read(self, size: int = -1):
        self.reads += 1
        size = self._step if size < 0 else min(size, self._step)
        out, self._data = self._data[:size], self._data[size:]
        return self._as_type(out)


DATA = bytes(range(256)) * 40


@pytest.mark.parametrize("step", [1, 7, 1000, len(DATA) - 1])
def test_gathers_a_stream_that_dribbles(step):
    stream = Dribble(DATA, step)
    assert read_exactly(stream, len(DATA)) == DATA
    assert stream.reads == -(-len(DATA) // step)


@pytest.mark.parametrize("as_type", [bytes, bytearray, memoryview])
def test_one_read_that_has_it_all_is_not_gathered(as_type):
    stream = Dribble(DATA + b"rest", len(DATA), as_type)
    got = read_exactly(stream, len(DATA))
    assert type(got) is bytes and got == DATA and stream.reads == 1
    assert read_exactly(stream, 4) == b"rest"


def test_whole_read_hands_on_the_streams_own_bytes():
    class Whole:
        def read(self, size):
            return DATA

    assert read_exactly(Whole(), len(DATA)) is DATA


@pytest.mark.parametrize("have,step", [(0, 10), (99, 10), (99, 1000)])
def test_short_stream_raises_with_what_it_got(have, step):
    with pytest.raises(EOFError, match=f"wanted 100, got {have}"):
        read_exactly(Dribble(DATA[:have], step), 100)


def test_none_from_a_stream_that_would_block_is_a_short_stream():
    class WouldBlock:
        def read(self, size):
            return None

    with pytest.raises(EOFError, match="wanted 5, got 0"):
        read_exactly(WouldBlock(), 5)


def test_nothing_wanted_is_nothing_read():
    assert read_exactly(io.BytesIO(DATA), 0) == b""


def test_over_a_bounded_file_range(tmp_path):
    path = tmp_path / "object"
    path.write_bytes(DATA)
    f = open(path, "rb")
    f.seek(100)
    with BoundedStream(f, 1000) as stream:
        assert read_exactly(stream, 400) == DATA[100:500]
        assert read_exactly(stream, 600) == DATA[500:1100]
        with pytest.raises(EOFError):
            read_exactly(stream, 1)
    assert f.closed
