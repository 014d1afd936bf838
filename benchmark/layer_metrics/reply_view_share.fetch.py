"""Gateway: percent of the streamed replies' body bytes that reached the socket as views of the objects the fetch tiers returned
(`SidecarHttpGateway.reply_bytes_as_views` over `.reply_bytes_sent`, exact): 100 where no block of a fetch's reply is copied in
user space on its way out, 0 where every block is read out of the stream into `bytes` first."""
from _spans import counted


def read(observation):
    as_views, sent = counted(observation, "reply_bytes_as_views"), counted(observation, "reply_bytes_sent")
    return 100.0 * as_views / sent if as_views is not None and sent else None
