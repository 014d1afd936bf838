"""Control of the S3 copy cell: the program leaves one part of every
multipart upload out (the second: never sent, never listed at Complete), as
an upload path that lost a part would; every upload, so that whichever copies
the check draws it meets one. The store completes the upload (part numbers
need only ascend) and the object is 5 MiB short. It breaks "the stored
objects are upstream's wire format: the plain reference reads them back"."""

DROPPED = 2


def apply() -> None:
    from tieredstorage_tpu.storage.s3.multipart import S3MultiPartOutputStream

    flush = S3MultiPartOutputStream._flush_part

    def flush_but_one(self, data):
        if self._part_number + 1 == DROPPED:
            self._part_number += 1
            return
        flush(self, data)

    S3MultiPartOutputStream._flush_part = flush_but_one
