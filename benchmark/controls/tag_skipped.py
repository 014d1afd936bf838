"""Control of the copy cells: the program stores every chunk with a tag of
zeros, as a copy path that left GHASH out would. It breaks "the stored objects
are upstream's wire format: the plain reference reads them back"."""

TAG = 16


def apply() -> None:
    from tieredstorage_tpu.transform.tpu import TpuTransformBackend

    finish = TpuTransformBackend._encrypt_finish

    def finish_without_tags(self, staged):
        return [chunk[:-TAG] + bytes(TAG) for chunk in finish(self, staged)]

    TpuTransformBackend._encrypt_finish = finish_without_tags
