"""Closed-loop copies: `clients` RLM task threads, each sending the run's
segment under a fresh segment id and waiting for `/v1/copy` to be
acknowledged before its next.

Parameters (the mix's file): `clients`; `max_copies`, a cap on the copies a
window starts (and so on what it writes); `check_copies`, how many of the
acknowledged copies the plain reference reads back (the last always, the rest
drawn from the seed).

The window: no copy starts after `--seconds`; it ends when the last copy in
flight is acknowledged, and the rate is all acknowledged log-segment bytes over
that time, so it does not move in steps of one copy. In a traced run the
second copy the window starts runs under the profiler.
"""

from __future__ import annotations

import contextlib
import random
import threading
import time

GIB = 1 << 30


def copy_rate_gib_s(acknowledged_bytes: int, window_start: float, last_ack: float) -> float:
    return acknowledged_bytes / GIB / (last_ack - window_start)


class Traffic:
    def __init__(self, bench) -> None:
        self.bench = bench
        self.params = bench.traffic["parameters"]
        self.acknowledged: list[int] = []  # ordinals, in the order acknowledged
        self.copy_seconds: list[float] = []
        self.failures: list[str] = []

    def set_up(self) -> None:
        bench, h = self.bench, self.bench.harness
        n_bytes = bench.sizes["segment_bytes"]
        self.segment = h.make_segment(bench.seed, n_bytes)
        self.indexes = h.make_indexes(bench.seed, n_bytes)
        deployment = bench.deploy()
        self.clients = [deployment.client() for _ in range(self.params["clients"])]
        # Warm-up: the same request as the timed ones, so every shape of the
        # window (full and ragged 16-row windows, one row per index) is loaded.
        self._copy(self.clients[0], 0)

    def _name(self, ordinal: int):
        return self.bench.reference.SegmentName.seeded(self.bench.seed, ordinal)

    def _copy(self, client, ordinal: int) -> float:
        md = self.bench.harness.segment_metadata(self._name(ordinal), len(self.segment))
        return client.copy(md, self.segment, self.indexes)

    def window(self) -> dict:
        bench = self.bench
        lock = threading.Lock()
        started = [0]
        last_ack = [0.0]

        traced_ordinal = min(2, self.params["max_copies"])

        def loop(client) -> None:
            while True:
                with lock:
                    if (started[0] >= self.params["max_copies"]
                            or time.perf_counter() - start >= bench.seconds):
                        return
                    started[0] += 1
                    ordinal = started[0]  # ordinal 0 was the warm-up
                traced = bench.stretch() if ordinal == traced_ordinal else contextlib.nullcontext()
                try:
                    with traced:
                        seconds = self._copy(client, ordinal)
                except bench.harness.Failed as exc:
                    with lock:
                        self.failures.append(str(exc))
                    continue
                with lock:
                    last_ack[0] = time.perf_counter()
                    self.acknowledged.append(ordinal)
                    self.copy_seconds.append(round(seconds, 4))

        threads = [
            threading.Thread(target=loop, args=(c,), name=f"rlm-task-{i}")
            for i, c in enumerate(self.clients)
        ]
        bench.open_window()
        start = time.perf_counter()
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join()
        for client in self.clients:
            client.close()
        for failure in self.failures[:5]:
            bench.harness.emit({"failed": failure})
        if not self.acknowledged:
            raise bench.harness.refuse("no copy was acknowledged in the window")
        copied = len(self.acknowledged) * len(self.segment)
        stored = sum(
            p.stat().st_size for p in bench.store_root.rglob("*") if p.is_file()
        )
        bench.close_window(
            seconds=last_ack[0] - start, copies=len(self.acknowledged),
            bytes=copied, bytes_stored=stored, copy_seconds=self.copy_seconds,
        )
        return {
            "attempted": started[0],
            "failed": len(self.failures),
            "metrics": {"copy_gib_s": copy_rate_gib_s(copied, start, last_ack[0])},
        }

    def check(self) -> dict:
        """Every sampled copy, read back from its three stored objects by the
        plain reference, against what was sent. Exact: each limit is 0."""
        bench = self.bench
        rng = random.Random(bench.seed)
        others = self.acknowledged[:-1]
        sample = sorted(
            rng.sample(others, min(len(others), self.params["check_copies"] - 1))
            + self.acknowledged[-1:]
        )
        unreadable = indexes_differ = 0
        keys = []
        for ordinal in sample:
            try:
                stored = bench.reference.read_segment(
                    bench.store_root, self._name(ordinal), bench.key
                )
            except Exception as exc:  # noqa: BLE001: any failure to read is the finding
                bench.harness.emit({
                    "check": "copy", "ordinal": ordinal,
                    "error": f"{type(exc).__name__}: {exc}"[:300],
                })
                unreadable += 1
                continue
            keys.append(stored.data_key)
            unreadable += stored.segment != self.segment
            indexes_differ += sum(
                stored.indexes[name] != blob for name, blob in self.indexes.items()
            )
        bench.harness.emit({"check": "copy", "read_back": sample})
        return {
            "copies_unreadable": {"value": unreadable, "limit": 0},
            "index_sections_differ": {"value": indexes_differ, "limit": 0},
            "data_keys_reused": {"value": len(keys) - len(set(keys)), "limit": 0},
        }

