"""`copy_closed_loop` against a store that is a service: the same copies,
window and check, with the S3 endpoint started before the deployment
(`_s3_store.py`) and its journal held to the configuration's guarantees
afterwards: no multipart upload left open, no request refused, the manifest's
Put the last request of every acknowledged copy that changes the store, no
part but an object's last under 5 MiB (each limit 0)."""

from __future__ import annotations

import _s3_store
import copy_closed_loop as base


class Traffic(base.Traffic):
    def __init__(self, bench) -> None:
        super().__init__(bench)
        self.store = _s3_store.S3Store(bench)

    def check(self) -> dict:
        compared = super().check()
        compared.update(self.store.compared([self._name(o) for o in self.acknowledged]))
        return compared
