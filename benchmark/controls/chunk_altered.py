"""Fault: an answer altered where it is produced. One bit of the first chunk
of every window differs, in what an encrypt window hands to the store and in
what a decrypt window hands to the gateway."""


def _flip(chunks: list, at: int) -> list:
    first = bytearray(chunks[0])
    first[at] ^= 0x01
    return [bytes(first)] + list(chunks[1:])


def apply() -> None:
    from tieredstorage_tpu.transform.tpu import TpuTransformBackend

    finish, decrypt = TpuTransformBackend._encrypt_finish, TpuTransformBackend._decrypt_window

    def finish_altered(self, staged):
        return _flip(finish(self, staged), 12 + 5)  # past the IV: ciphertext

    def decrypt_altered(self, *args, **kwargs):
        return _flip(decrypt(self, *args, **kwargs), 5)

    TpuTransformBackend._encrypt_finish = finish_altered
    TpuTransformBackend._decrypt_window = decrypt_altered
