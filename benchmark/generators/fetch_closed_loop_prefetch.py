"""`fetch_closed_loop` for a deployment whose chunk cache prefetches: the same
requests, window and check, with a warm-up that also meets the decrypt windows
a prefetching cache makes.

Under `fetch.chunk.cache.prefetch.max.size` a chunk is decrypted by a prefetch
task in sub-windows of `prefetch.window.chunks` (2) rows, so a scan launches
four window programs where the parent generator's canary meets two: one full
row (a foreground miss, a lone new chunk ahead of the reader), one ragged row
(a segment's last chunk, alone), two full rows (a segment's entry) and a full
row with the ragged one (a segment's last two chunks, new together). Two
segments more are stored that the timed reads never reach, and each is read
from position 0, chunk by chunk, so that every load has landed before
`open_window()`: five full chunks and a ragged one (one row, two full rows
twice, the ragged row alone), and two full chunks and a ragged one (the
varlen pair). Each reply is compared with the source like the canary's.

The window also counts the distinct chunks that the answered requests' wanted
bytes span (`chunks_reached`), which the readers of the decrypts per chunk
divide by: what the prefetcher decrypted ahead of the reader is in the
dividend and not here.
"""

from __future__ import annotations

import fetch_closed_loop as base

#: Full chunks before the ragged one in each warm-up segment.
WARM_UP_SEGMENTS = (5, 2)


class Traffic(base.Traffic):
    def set_up(self) -> None:
        super().set_up()
        bench, h, ref = self.bench, self.bench.harness, self.bench.reference
        indexes = h.make_indexes(bench.seed, self.segment_bytes)
        ragged = self.segment_bytes % self.chunk or self.chunk
        small = self.read_bytes // 4
        for nth, full_chunks in enumerate(WARM_UP_SEGMENTS, start=1):
            name = ref.SegmentName.seeded(bench.seed, self.params["segments"] + nth)
            n_bytes = full_chunks * self.chunk + ragged
            ref.write_segment(
                bench.store_root, name, bench.key, h.KEY_ID,
                self.segment[:n_bytes], indexes, self.chunk,
            )
            md = h.segment_metadata(name, n_bytes)
            for start in range(0, n_bytes, self.chunk):
                due = min(small, n_bytes - start)
                got, _ = self.clients[0].fetch_tail(md, start, due)
                self.warm_up_differ += got != self.segment[start : start + due]

    def window(self) -> dict:
        measured = super().window()
        reached = {
            (segment, chunk)
            for segment, position in self.sent
            for chunk in range(
                position // self.chunk,
                (position + self._due(position) - 1) // self.chunk + 1,
            )
        }
        self.bench.observation["window"]["chunks_reached"] = len(reached)
        self.bench.harness.emit({"phase": "window", "chunks_reached": len(reached)})
        return measured
