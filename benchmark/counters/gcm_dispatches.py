"""`ops.gcm.device_dispatches()`: every GCM program launched in this process (exact)."""


def read(deployment) -> dict:
    from tieredstorage_tpu.ops import gcm

    return {"gcm_dispatches": gcm.device_dispatches()}
