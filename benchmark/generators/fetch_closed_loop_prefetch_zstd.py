"""`fetch_closed_loop_prefetch` over segments that were compressed before they
were encrypted: the same requests, window, warm-up and check, with only what
compression needs.

**The writer.** Set-up stores its segments with `reference_zstd.write_segment`
(each chunk a zstd frame, then sealed; a `variable` chunk index) in place of
`reference.write_segment`; positions and replies stay those of the original
bytes, as a broker's are.

**The canary.** The parent flips a bit of chunk 0's ciphertext. Under
compression the codec may refuse the frame that decrypts from it on its own,
so a program that skipped the tag check could still come out correct. Here
that bit is put back and one bit of chunk 0's 16-byte tag is flipped instead:
the ciphertext, and so the frame, is whole, and only the tag check can refuse
the chunk. (The warm-up reads chunks 1 and 2 and the prefetcher only reads
ahead, so chunk 0 is first asked for after the window.)

**The refusal.** Every compressed chunk has a size of its own. A program that
gives a one-row decrypt window a fixed-shape program per byte size would
trace and compile one per chunk: 64 a segment, half a minute each cold. So
between the parent generators' two warm-ups (after the canary's clean full
chunk and ragged chunk have each been read through a one-row window) one more
full chunk of another compressed size is read alone, with nothing else in
flight, and if that read traced a program (`utils.platforms.
program_trace_stats()`) the run is refused at once, with a sentence saying
so. `_Probe` sits between the two parents in the method resolution order so
that this happens there and not after the whole warm-up.
"""

from __future__ import annotations

import fetch_closed_loop
import fetch_closed_loop_prefetch as base
import reference_zstd


class _Probe(fetch_closed_loop.Traffic):
    """`fetch_closed_loop`'s set-up, then the canary's repair and the read
    that a program without a ladder for one-row windows fails."""

    def set_up(self) -> None:
        super().set_up()
        self._move_canary_fault_to_the_tag()
        self._refuse_a_program_per_chunk_size()

    def _move_canary_fault_to_the_tag(self) -> None:
        bench, ref = self.bench, self.bench.reference
        canary = ref.SegmentName.seeded(bench.seed, self.params["segments"])
        self.stored = ref.stored_sizes(bench.store_root, canary)
        with open(canary.path(bench.store_root, "log"), "r+b") as log:
            for at in (ref.IV + self.chunk // 2, self.stored[0] - ref.TAG // 2):
                log.seek(at)
                byte = log.read(1)
                log.seek(-1, 1)
                log.write(bytes([byte[0] ^ 0x01]))

    def _refuse_a_program_per_chunk_size(self) -> None:
        from tieredstorage_tpu.utils import platforms

        bench, h, ref = self.bench, self.bench.harness, self.bench.reference
        # The canary is the source's first chunks, so `stored` has their sizes:
        # chunk 1 went through a one-row window in the warm-up; chunk 0 is as
        # full and, unless the two compressed alike, of another size.
        if self.stored[0] == self.stored[1]:
            return
        name = ref.SegmentName.seeded(bench.seed, self.params["segments"] + 3)
        ref.write_segment(
            bench.store_root, name, bench.key, h.KEY_ID, self.segment[: self.chunk],
            h.make_indexes(bench.seed, self.segment_bytes), self.chunk,
        )
        small = self.read_bytes // 4
        before = platforms.program_trace_stats()["program_traces"]
        got, _ = self.clients[0].fetch_tail(h.segment_metadata(name, self.chunk), 0, small)
        traced = platforms.program_trace_stats()["program_traces"] - before
        self.warm_up_differ += got != self.segment[:small]
        h.emit({
            "phase": "one_row_probe", "stored_bytes": [self.stored[1], self.stored[0]],
            "programs_traced": traced,
        })
        if traced:
            raise h.refuse(
                f"a one-row decrypt of a compressed chunk of {self.stored[0]} stored bytes "
                f"traced {traced} program(s) of its own after one of {self.stored[1]} had "
                "run: this program compiles one-row windows per chunk size and not per "
                "rung of a ladder, which under compression is a program for every chunk "
                "of every segment; the cell cannot run on it"
            )


class Traffic(base.Traffic, _Probe):
    def __init__(self, bench) -> None:
        # On the instance, so that the parents' set-up writes compressed
        # segments; `Bench.reference` itself stays the plain reference.
        bench.reference = reference_zstd
        super().__init__(bench)
