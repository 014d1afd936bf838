"""The device hot tier's hits, misses and admissions, one per chunk read (exact); noughts where the deployment has no such tier."""


def read(deployment) -> dict:
    hot = deployment.rsm.device_hot_cache
    return {
        "hot_hits": hot.hits if hot is not None else 0,
        "hot_misses": hot.misses if hot is not None else 0,
        "hot_admissions": hot.admissions if hot is not None else 0,
    }
