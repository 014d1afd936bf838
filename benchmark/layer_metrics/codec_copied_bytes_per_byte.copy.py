"""Host codec: bytes the host copied into fresh memory around the compress codec, per source byte handed to it
(`DispatchStats.codec_bytes_copied` over `.codec_bytes_in`, exact): 0.0 where each chunk is compressed where it lies and the window's
pack reads the frames as views of a reused buffer, ~1.78 where the input is gathered and every frame copied out; nothing without a codec."""
from _spans import counted


def read(observation):
    copied, handed = counted(observation, "codec_bytes_copied"), counted(observation, "codec_bytes_in")
    return copied / handed if copied is not None and handed else None
