"""Gateway: bytes the gateway wrote to local files before the RSM was called, per byte of `/v1/copy` body it received
(`SidecarHttpGateway.copy_body_bytes_written` over `.copy_body_bytes`, exact): 1.0 where each section goes from the socket to the
file the RSM opens, 2.0 where the body is held in a file of its own first."""
from _spans import counted


def read(observation):
    written, received = counted(observation, "copy_body_bytes_written"), counted(observation, "copy_body_bytes")
    return written / received if written is not None and received else None
