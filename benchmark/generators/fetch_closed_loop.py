"""Closed-loop remote reads: `clients` remote-log-reader threads, each making
the call that Kafka's `RemoteLogManager.read` makes and waiting for its bytes
before the next.

That call is the open-ended `fetchLogSegment(metadata, startPosition)` (the
shim's `encodeFetchTail(start, null)`): the broker reads about
`max.partition.fetch.bytes` from the stream and closes it. The consumer's next
fetch starts where the last whole batch ended, looked up in the offset index.

Parameters (the mix's file): `clients`; `read_bytes`, what the reader takes of
each reply before it closes; `step_bytes`, how far the start position advances
from one request to the next (a segment's last request is cut short by the
segment's end, and the next starts at 0 of the following segment); `segments`,
how many the plain reference stores in set-up (all of the run's one plaintext,
each under its own data key); `first_request`, which of a segment's requests a
client starts with, in a segment drawn from the seed, so every seed sends the
same requests; `check_one_in`, the share of requests, drawn from the seed,
whose reply is kept and compared with the source once the window has closed
(every reply's length is compared; a request that reaches a segment's ragged
last chunk is always kept); `stretch_after` and `stretch_seconds`, where the
traced run's profiled stretch lies.

One segment more is stored that the reads never reach: a full chunk whose
ciphertext set-up alters by one bit, a full chunk and a ragged one left as
they are. The two clean chunks are the warm-up (both chunk shapes, each read
cold, on its second touch and from the hot tier); after the window the altered
one has to be refused, or the configuration's "every fetched chunk's GCM tag
is verified" is broken.
"""

from __future__ import annotations

import concurrent.futures
import contextlib
import random
import re
import threading
import time

MIB = 1 << 20
UPLOAD_THREADS = 6
PERCENTILE_METRIC = re.compile(r"fetch_p(\d+)_ms")


def percentile(sorted_values: list, q: float) -> float:
    """Linear interpolation between closest ranks, over an ascending list."""
    at = q * (len(sorted_values) - 1)
    low = int(at)
    high = min(low + 1, len(sorted_values) - 1)
    return sorted_values[low] + (sorted_values[high] - sorted_values[low]) * (at - low)


class Traffic:
    def __init__(self, bench) -> None:
        self.bench = bench
        self.params = bench.traffic["parameters"]
        self.chunk = bench.sizes["chunk_bytes"]
        self.segment_bytes = bench.sizes["segment_bytes"]
        self.read_bytes = self.params["read_bytes"]
        self.ragged_from = (self.segment_bytes - 1) // self.chunk * self.chunk
        self.latencies: list[float] = []
        self.sent: list[tuple[int, int]] = []  # (segment, position), as answered
        self.kept: list[tuple[int, bytes]] = []
        self.wrong_length = 0
        self.warm_up_differ = 0
        self.failures: list[str] = []

    # ---------------------------------------------------------- the requests
    def _next(self, segment: int, start: int) -> tuple[int, int]:
        start += self.params["step_bytes"]
        if start >= self.segment_bytes:
            return (segment + 1) % self.params["segments"], 0
        return segment, start

    def _due(self, start: int) -> int:
        return min(self.read_bytes, self.segment_bytes - start)

    def set_up(self) -> None:
        bench, h, ref = self.bench, self.bench.harness, self.bench.reference
        self.segment = h.make_segment(bench.seed, self.segment_bytes)
        indexes = h.make_indexes(bench.seed, self.segment_bytes)
        started = time.perf_counter()
        # AES-GCM and file writes release the interpreter lock: a few threads
        # store the segments in a quarter of the time one takes.
        with concurrent.futures.ThreadPoolExecutor(max_workers=UPLOAD_THREADS) as pool:
            for done in [
                pool.submit(
                    ref.write_segment, bench.store_root,
                    ref.SegmentName.seeded(bench.seed, ordinal),
                    bench.key, h.KEY_ID, self.segment, indexes, self.chunk,
                )
                for ordinal in range(self.params["segments"])
            ]:
                done.result()
        # The canary: altered full chunk, clean full chunk, clean ragged chunk.
        canary = ref.SegmentName.seeded(bench.seed, self.params["segments"])
        ragged = self.segment_bytes % self.chunk or self.chunk
        self.canary_bytes = 2 * self.chunk + ragged
        ref.write_segment(
            bench.store_root, canary, bench.key, h.KEY_ID,
            self.segment[: self.canary_bytes], indexes, self.chunk,
        )
        with open(canary.path(bench.store_root, "log"), "r+b") as log:
            log.seek(ref.IV + self.chunk // 2)
            byte = log.read(1)
            log.seek(-1, 1)
            log.write(bytes([byte[0] ^ 0x01]))
        self.canary_md = h.segment_metadata(canary, self.canary_bytes)
        h.emit({
            "phase": "reference_upload", "segments": self.params["segments"] + 1,
            "seconds": round(time.perf_counter() - started, 3),
            "bytes": self.params["segments"] * self.segment_bytes + self.canary_bytes,
        })
        self.metadata = [
            h.segment_metadata(ref.SegmentName.seeded(bench.seed, o), self.segment_bytes)
            for o in range(self.params["segments"])
        ]
        rng = random.Random(bench.seed)
        self.first_segment = rng.randrange(self.params["segments"])
        self.keep_draw = random.Random(rng.random())
        deployment = bench.deploy()
        self.clients = [deployment.client() for _ in range(self.params["clients"])]
        # Three reads inside the clean full chunk, two inside the ragged one.
        small = self.read_bytes // 4
        for start in (self.chunk, self.chunk + small, self.chunk + 2 * small,
                      2 * self.chunk, 2 * self.chunk + small):
            due = min(small, self.canary_bytes - start)
            got, _ = self.clients[0].fetch_tail(self.canary_md, start, due)
            self.warm_up_differ += got != self.segment[start : start + due]

    # ------------------------------------------------------------- the window
    def window(self) -> dict:
        bench = self.bench
        lock = threading.Lock()
        attempted = [0]
        fetched_bytes = [0]
        last_reply = [0.0]
        segments_entered = [0]
        stretch = contextlib.ExitStack()
        stretch_ends = [None]

        def loop(index: int, client) -> None:
            segment = (
                self.first_segment + index * self.params["segments"] // len(self.clients)
            ) % self.params["segments"]
            position = self.params["first_request"] * self.params["step_bytes"]
            mine = 0
            while time.perf_counter() - start < bench.seconds:
                if index == 0 and bench.trace:
                    now = time.perf_counter()
                    if mine == self.params["stretch_after"]:
                        stretch.enter_context(bench.stretch())
                        stretch_ends[0] = time.perf_counter() + self.params["stretch_seconds"]
                    elif stretch_ends[0] is not None and now >= stretch_ends[0]:
                        stretch.close()
                        stretch_ends[0] = None
                mine += 1
                with lock:
                    attempted[0] += 1
                    segments_entered[0] += position == 0
                    keep = (
                        self.keep_draw.randrange(self.params["check_one_in"]) == 0
                        or position + self.read_bytes > self.ragged_from
                    )
                try:
                    body, seconds = client.fetch_tail(
                        self.metadata[segment], position, self.read_bytes
                    )
                except bench.harness.Failed as exc:
                    with lock:
                        self.failures.append(str(exc))
                    segment, position = self._next(segment, position)
                    continue
                with lock:
                    last_reply[0] = time.perf_counter()
                    self.latencies.append(seconds)
                    self.sent.append((segment, position))
                    fetched_bytes[0] += len(body)
                    self.wrong_length += len(body) != self._due(position)
                    if keep:
                        self.kept.append((position, body))
                segment, position = self._next(segment, position)

        threads = [
            threading.Thread(target=loop, args=(i, c), name=f"remote-log-reader-{i}")
            for i, c in enumerate(self.clients)
        ]
        bench.open_window()
        start = time.perf_counter()
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join()
        stretch.close()
        for failure in self.failures[:5]:
            bench.harness.emit({"failed": failure})
        if len(self.latencies) < 2:
            raise bench.harness.refuse("fewer than two fetches were answered in the window")
        seconds = last_reply[0] - start
        ordered = sorted(self.latencies)
        tails = {
            name: 1e3 * percentile(ordered, int(match.group(1)) / 100)
            for name in bench.end_to_end
            if (match := PERCENTILE_METRIC.fullmatch(name))
        }
        bench.close_window(
            seconds=seconds, fetches=len(self.latencies), bytes=fetched_bytes[0],
            segments_entered=segments_entered[0],
            wrapped=segments_entered[0] > self.params["segments"],
            latency_ms={
                **tails, "mean": 1e3 * sum(ordered) / len(ordered), "max": 1e3 * ordered[-1],
                "every_50th_of_sorted": [round(1e3 * v, 2) for v in ordered[::50]],
            },
            slowest=[  # [answered as the n-th, segment, position, ms]
                [n, *self.sent[n], round(1e3 * self.latencies[n], 1)]
                for n in sorted(range(len(self.latencies)), key=self.latencies.__getitem__)[-6:]
            ],
        )
        # After the window, before the deployment is freed: the canary.
        self.canary = self._ask_canary(self.clients[0])
        return {
            "attempted": attempted[0],
            "failed": len(self.failures),
            "metrics": {**tails, "fetch_mib_s": fetched_bytes[0] / MIB / seconds},
        }

    def _ask_canary(self, client) -> int:
        """1 where the altered chunk was served. The clean chunks beside it
        were served in the warm-up, so a refusal here is the tag's (the
        program then quarantines the object for a while, which is why nothing
        else is asked of it afterwards)."""
        bench = self.bench
        try:
            client.fetch_tail(self.canary_md, 0, self.read_bytes)
            return 1
        except bench.harness.Failed as exc:
            bench.harness.emit({"check": "altered chunk refused", "answer": str(exc)[:200]})
            return 0

    # --------------------------------------------------------------- the check
    def check(self) -> dict:
        """The kept replies against the source bytes: exact, each limit 0."""
        source = memoryview(self.segment)
        differ = self.warm_up_differ
        for position, body in self.kept:
            differ += source[position : position + self._due(position)] != body
        self.bench.harness.emit({
            "check": "fetch", "replies_compared": len(self.kept),
            "bytes_compared": sum(len(b) for _, b in self.kept),
        })
        return {
            "replies_differ": {"value": differ, "limit": 0},
            "replies_wrong_length": {"value": self.wrong_length, "limit": 0},
            "altered_chunk_served": {"value": self.canary, "limit": 0},
        }
