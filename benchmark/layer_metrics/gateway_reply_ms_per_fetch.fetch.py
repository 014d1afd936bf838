"""Gateway: the reply loop's own time (`gateway.reply_stream` `self_s`: first block pulled to last write, less the chunk reads and
decrypts the lazy stream pulls, which are its child spans), blocks read on for a reader that has left included; milliseconds per
answered fetch."""
from _spans import ms_per_fetch


def read(observation):
    return ms_per_fetch(observation, ("gateway.reply_stream",), "self_s")
