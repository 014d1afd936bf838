"""`SidecarHttpGateway.reply_bytes_sent` and `.reply_bytes_as_views`: body bytes of the streamed replies (`/v1/fetch`, `/v1/fetch-index`; aborted ones too) that the kernel took, and those of them handed to it as views of what the fetch tiers returned (exact); nothing where the program has no such counts."""


def read(deployment) -> dict:
    gateway = deployment.gateway
    sent = getattr(gateway, "reply_bytes_sent", None)
    as_views = getattr(gateway, "reply_bytes_as_views", None)
    if sent is None or as_views is None:
        return {}
    return {"reply_bytes_sent": sent, "reply_bytes_as_views": as_views}
