"""`fetch_closed_loop` for a broker's pool of remote-log readers: `clients`
closed loops at once, each on its own partition's segments (reader i reads
`segments / clients` of them in turn, each under its own data key), through a
deployment whose cross-request batcher merges concurrent decrypt windows.

Reader i starts at request `(first_request + request_stride * i)` of its first
segment, modulo a segment's requests, so that segment ends, new keys and
their context builds do not arrive together. A reader that would enter another
reader's first segment refuses the run: its partition's segments are too few
for the pace. Each reader's segments entered are printed.

Set-up makes the batcher launch every merged shape the window can launch, on
one-chunk segments the window never reads (the ragged chunk's size, on the
same rung of `bucket_max_bytes` as a full chunk): the fetches of a round are
held in the batcher's queue (its inline path parked, its wait stretched) until
every one's window is there, then flushed together; 9 keys at once, 2, and 1
alone, each chunk twice, so that the hot tier admits it from a merged launch.
A program without key tables launches them one key at a time (an 8-row
window each), and the same warm-up warms its shapes.

After the window the canary's altered chunk is asked for in one such round
with clean reads of three other keys: it has to be refused
(`altered_chunk_served`) and they have to be served (`batch_mates_failed`)
and compare equal to the source.
"""

from __future__ import annotations

import contextlib
import threading
import time

import fetch_closed_loop as base

#: Keys a warm-up round merges: nine, two and one key alone.
WARM_KEYS = (9, 2, 1)
#: Clean reads of other keys beside the canary.
CANARY_MATES = 3
#: How long a held round may take to reach the queue.
HOLD_TIMEOUT_S = 300.0


class Traffic(base.Traffic):
    def __init__(self, bench) -> None:
        super().__init__(bench)
        p = self.params
        self.per_reader = p["segments"] // p["clients"]
        self.requests_per_segment = -(-self.segment_bytes // p["step_bytes"])
        self.batch_mates_failed = 0

    # ------------------------------------------------------------- set-up
    def set_up(self) -> None:
        super().set_up()
        bench, h, ref = self.bench, self.bench.harness, self.bench.reference
        indexes = h.make_indexes(bench.seed, self.segment_bytes)
        ragged = self.segment_bytes % self.chunk or self.chunk
        self.small = self.read_bytes // 4
        warm = []
        for j in range(sum(WARM_KEYS) + CANARY_MATES):
            name = ref.SegmentName.seeded(bench.seed, self.params["segments"] + 1 + j)
            ref.write_segment(
                bench.store_root, name, bench.key, h.KEY_ID,
                self.segment[:ragged], indexes, self.chunk,
            )
            warm.append(h.segment_metadata(name, ragged))
        started = time.perf_counter()
        at = 0
        for keys in WARM_KEYS:
            for _touch in range(2):
                self._compare(self._held([(md, 0, self.small) for md in warm[at : at + keys]]))
            at += keys
        self.canary_mates = warm[at:]
        h.emit({"phase": "merged_warm_up", "rounds": 2 * len(WARM_KEYS),
                "seconds": round(time.perf_counter() - started, 3)})

    def _held(self, reads: list) -> list:
        """Fetch `reads` at once, one client each, held in the batcher's
        queue until every one's decrypt window is there (a deployment without
        a batcher just sends them at once); [(body, seconds) or the failure]
        in order."""
        batcher = self.bench.deployment.backend.batcher
        results: list = [None] * len(reads)

        def one(i: int) -> None:
            try:
                results[i] = self.clients[i].fetch_tail(*reads[i])
            except self.bench.harness.Failed as exc:
                results[i] = exc

        threads = [threading.Thread(target=one, args=(i,)) for i in range(len(reads))]
        if batcher is None:
            for thread in threads:
                thread.start()
            for thread in threads:
                thread.join()
            return results
        with batcher._cond:
            batcher._inflight += 1  # no inline dispatch: every window queues
            wait_ms, batcher.wait_ms = batcher.wait_ms, 1e9
        for thread in threads:
            thread.start()
        deadline = time.monotonic() + HOLD_TIMEOUT_S
        try:
            while True:
                with batcher._cond:
                    if sum(len(q) for q in batcher._buckets.values()) >= len(reads):
                        break
                if time.monotonic() > deadline or not any(t.is_alive() for t in threads):
                    raise self.bench.harness.refuse("a held round never reached the queue")
                time.sleep(0.001)
        finally:
            with batcher._cond:
                batcher._inflight -= 1
                batcher.wait_ms = wait_ms
                batcher._cond.notify_all()
            for thread in threads:
                thread.join()
        return results

    def _compare(self, results: list) -> int:
        """Failures among `results`; each served body is compared with the
        source (a one-chunk segment's reads start at 0)."""
        failed = 0
        for got in results:
            if isinstance(got, Exception):
                failed += 1
            else:
                self.warm_up_differ += got[0] != self.segment[: len(got[0])]
                self.wrong_length += len(got[0]) != self.small
        return failed

    # ------------------------------------------------------------- the window
    def window(self) -> dict:
        bench, p = self.bench, self.params
        n = len(self.clients)
        firsts = [(self.first_segment + self.per_reader * i) % p["segments"] for i in range(n)]
        lock = threading.Lock()
        attempted, fetched_bytes, last_reply = [0], [0], [0.0]
        entered = [0] * n
        overran: list = []
        stretch = contextlib.ExitStack()
        stretch_ends = [None]
        opened = [False]

        def loop(index: int, client) -> None:
            segment = firsts[index]
            request = (p["first_request"] + p["request_stride"] * index) % self.requests_per_segment
            position = request * p["step_bytes"]
            others = set(firsts) - {segment}
            while time.perf_counter() - start < bench.seconds:
                if index == 0 and bench.trace:
                    # Reader 0 opens the stretch once the readers together
                    # have sent `stretch_after` requests, and closes it.
                    now = time.perf_counter()
                    if not opened[0] and attempted[0] >= p["stretch_after"]:
                        opened[0] = True
                        stretch.enter_context(bench.stretch())
                        stretch_ends[0] = time.perf_counter() + p["stretch_seconds"]
                    elif stretch_ends[0] is not None and now >= stretch_ends[0]:
                        stretch.close()
                        stretch_ends[0] = None
                with lock:
                    attempted[0] += 1
                    entered[index] += position == 0
                    keep = (
                        self.keep_draw.randrange(p["check_one_in"]) == 0
                        or position + self.read_bytes > self.ragged_from
                    )
                try:
                    body, seconds = client.fetch_tail(
                        self.metadata[segment], position, self.read_bytes
                    )
                except bench.harness.Failed as exc:
                    with lock:
                        self.failures.append(str(exc))
                else:
                    with lock:
                        last_reply[0] = time.perf_counter()
                        self.latencies.append(seconds)
                        self.sent.append((segment, position))
                        fetched_bytes[0] += len(body)
                        self.wrong_length += len(body) != self._due(position)
                        if keep:
                            self.kept.append((position, body))
                segment, position = self._next(segment, position)
                if position == 0 and segment in others:
                    with lock:
                        overran.append(index)
                    return

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
        bench.harness.emit({"phase": "window", "segments_entered_by_reader": entered})
        if overran:
            raise bench.harness.refuse(
                f"readers {sorted(overran)} reached another reader's first segment: "
                f"{p['segments']} segments are too few for the pace"
            )
        if len(self.latencies) < 2:
            raise bench.harness.refuse("fewer than two fetches were answered in the window")
        seconds = last_reply[0] - start
        ordered = sorted(self.latencies)
        tails = {
            name: 1e3 * base.percentile(ordered, int(match.group(1)) / 100)
            for name in bench.end_to_end
            if (match := base.PERCENTILE_METRIC.fullmatch(name))
        }
        bench.close_window(
            seconds=seconds, fetches=len(self.latencies), bytes=fetched_bytes[0],
            segments_entered=sum(entered), segments_entered_by_reader=entered,
            wrapped=bool(overran),
            latency_ms={
                **tails, "mean": 1e3 * sum(ordered) / len(ordered), "max": 1e3 * ordered[-1],
                "every_50th_of_sorted": [round(1e3 * v, 2) for v in ordered[::50]],
            },
            slowest=[
                [k, *self.sent[k], round(1e3 * self.latencies[k], 1)]
                for k in sorted(range(len(self.latencies)), key=self.latencies.__getitem__)[-6:]
            ],
        )
        # After the window, before the deployment is freed: the canary, in
        # one held round with clean reads of other keys.
        results = self._held(
            [(self.canary_md, 0, self.read_bytes)]
            + [(md, 0, self.small) for md in self.canary_mates]
        )
        self.canary = 0 if isinstance(results[0], Exception) else 1
        if not self.canary:
            bench.harness.emit({"check": "altered chunk refused", "answer": str(results[0])[:200]})
        self.batch_mates_failed = self._compare(results[1:])
        return {
            "attempted": attempted[0],
            "failed": len(self.failures),
            "metrics": {**tails, "fetch_mib_s": fetched_bytes[0] / base.MIB / seconds},
        }

    def check(self) -> dict:
        compared = super().check()
        compared["batch_mates_failed"] = {"value": self.batch_mates_failed, "limit": 0}
        return compared
