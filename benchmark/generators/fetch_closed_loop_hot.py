"""`fetch_closed_loop` over one hot segment: one closed-loop client whose every
request reads a chunk drawn by rank from Zipf(`zipf_s`) over the segment's
chunks, the ranks mapped to chunks by a permutation drawn from the seed, from
one of the chunk's first `starts_per_chunk` positions `step_bytes` apart,
drawn uniformly: many consumer groups re-reading one recently tiered segment.

The segment, the canary and the warm-up are `fetch_closed_loop`'s with
`segments` 1. A chunk is decrypted on its first touches and served from the
device hot tier once admitted, so the tail of the distribution is what keeps
the device busy: the profiled stretch starts after `stretch_after` requests,
while first and second touches of the tail still occur, and the decrypt rows
it held are printed. A request that reaches the ragged last chunk is always
kept for the check.
"""

from __future__ import annotations

import bisect
import contextlib
import itertools
import random
import time

import fetch_closed_loop as base


class Traffic(base.Traffic):
    def set_up(self) -> None:
        super().set_up()
        p = self.params
        chunks = -(-self.segment_bytes // self.chunk)
        rng = random.Random(self.bench.seed + 1)
        self.by_rank = list(range(chunks))
        rng.shuffle(self.by_rank)
        self.cumulative = list(itertools.accumulate(
            (rank + 1) ** -p["zipf_s"] for rank in range(chunks)
        ))
        self.draw = random.Random(rng.random())

    def _request(self) -> int:
        rank = bisect.bisect_right(
            self.cumulative, self.draw.random() * self.cumulative[-1]
        )
        chunk = self.by_rank[min(rank, len(self.by_rank) - 1)]
        start = self.draw.randrange(self.params["starts_per_chunk"]) * self.params["step_bytes"]
        return chunk * self.chunk + start

    def window(self) -> dict:
        bench, p = self.bench, self.params
        client, metadata = self.clients[0], self.metadata[0]
        fetched_bytes, chunks_touched = 0, set()
        attempted, last_reply = 0, 0.0
        stretch = contextlib.ExitStack()
        stretch_ends = None
        bench.open_window()
        start = time.perf_counter()
        while time.perf_counter() - start < bench.seconds:
            if bench.trace:
                if attempted == p["stretch_after"]:
                    stretch.enter_context(bench.stretch())
                    stretch_ends = time.perf_counter() + p["stretch_seconds"]
                elif stretch_ends is not None and time.perf_counter() >= stretch_ends:
                    stretch.close()
                    stretch_ends = None
            attempted += 1
            position = self._request()
            chunks_touched.add(position // self.chunk)
            keep = (
                self.keep_draw.randrange(p["check_one_in"]) == 0
                or position + self.read_bytes > self.ragged_from
            )
            try:
                body, seconds = client.fetch_tail(metadata, position, self.read_bytes)
            except bench.harness.Failed as exc:
                self.failures.append(str(exc))
                continue
            last_reply = time.perf_counter()
            self.latencies.append(seconds)
            self.sent.append((0, position))
            fetched_bytes += len(body)
            self.wrong_length += len(body) != self._due(position)
            if keep:
                self.kept.append((position, body))
        stretch.close()
        for failure in self.failures[:5]:
            bench.harness.emit({"failed": failure})
        if len(self.latencies) < 2:
            raise bench.harness.refuse("fewer than two fetches were answered in the window")
        seconds = last_reply - start
        ordered = sorted(self.latencies)
        tails = {
            name: 1e3 * base.percentile(ordered, int(match.group(1)) / 100)
            for name in bench.end_to_end
            if (match := base.PERCENTILE_METRIC.fullmatch(name))
        }
        bench.close_window(
            seconds=seconds, fetches=len(self.latencies), bytes=fetched_bytes,
            chunks_touched=len(chunks_touched),
            latency_ms={
                **tails, "mean": 1e3 * sum(ordered) / len(ordered), "max": 1e3 * ordered[-1],
                "every_50th_of_sorted": [round(1e3 * v, 2) for v in ordered[::50]],
            },
            slowest=[
                [k, *self.sent[k], round(1e3 * self.latencies[k], 1)]
                for k in sorted(range(len(self.latencies)), key=self.latencies.__getitem__)[-6:]
            ],
        )
        stretched = bench.observation.get("stretch")
        if stretched is not None:
            bench.harness.emit({
                "phase": "stretch", "decrypt_rows": stretched["counters"].get("rows"),
            })
        self.canary = self._ask_canary(client)
        return {
            "attempted": attempted,
            "failed": len(self.failures),
            "metrics": {**tails, "fetch_mib_s": fetched_bytes / base.MIB / seconds},
        }
