"""`fetch_closed_loop` against a store that is a service: the same requests,
window, canary and check, with the S3 endpoint started before set-up stores
the segments as files under its `<root>/<bucket>` (`_s3_store.py`), so that
every stored-chunk read of the program is a signed ranged GET, and the
endpoint's journal held to the configuration's guarantees afterwards (no
request refused; the three numbers of uploads read 0 where nothing is
uploaded)."""

from __future__ import annotations

import _s3_store
import fetch_closed_loop as base


class Traffic(base.Traffic):
    def __init__(self, bench) -> None:
        super().__init__(bench)
        self.store = _s3_store.S3Store(bench)

    def check(self) -> dict:
        compared = super().check()
        compared.update(self.store.compared([]))
        return compared
