"""`SidecarHttpGateway.copy_body_bytes` and `.copy_body_bytes_written`: bytes of the `/v1/copy` bodies the gateway received whole, and the bytes it wrote to local files before it called the RSM (exact); nothing where the program has no such counts."""


def read(deployment) -> dict:
    gateway = deployment.gateway
    received = getattr(gateway, "copy_body_bytes", None)
    written = getattr(gateway, "copy_body_bytes_written", None)
    if received is None or written is None:
        return {}
    return {"copy_body_bytes": received, "copy_body_bytes_written": written}
