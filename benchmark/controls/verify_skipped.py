"""Control of the fetch cells: the program serves a chunk without comparing
its tag, as a fetch path that left GHASH out would. It breaks "every fetched
chunk's GCM tag is verified before its bytes are served"."""

import types


def apply() -> None:
    from tieredstorage_tpu.transform import tpu

    tpu.hmac = types.SimpleNamespace(compare_digest=lambda expected, received: True)
