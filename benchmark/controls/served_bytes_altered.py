"""Fault: the bytes a fetch serves altered after the codec, for the cells whose
deployment compresses. One bit of the first chunk of every detransform differs
in what the chunk manager is handed.

`chunk_altered.py` flips byte 5 of what a decrypt window hands on. Under
compression that is a byte of the zstd frame's header, before the codec: at
4 MiB chunks the frame's window descriptor, which a decoder may read either
way and still give back the same bytes (the run then reads correct, and
rightly: nothing served differs), at sizes the compressor's window covers the
content size, which the codec refuses on its own. This one alters what comes
out of the codec, which nothing below the gateway checks again."""


def apply() -> None:
    from tieredstorage_tpu.transform.tpu import TpuTransformBackend

    detransform = TpuTransformBackend.detransform

    def detransform_altered(self, chunks, opts):
        plain = detransform(self, chunks, opts)
        if not plain:
            return plain
        first = bytearray(plain[0])
        first[5] ^= 0x01
        return [bytes(first)] + list(plain[1:])

    TpuTransformBackend.detransform = detransform_altered
