"""What the two `_s3` generators share: a store that is a service.

`S3Store(bench)` starts `benchmark/s3_endpoint.py` as a process of its own over
`<bench.tmp>/s3`, with the configuration's static keys, puts the one key only a
run knows (`storage.s3.endpoint.url`) into the configuration's `rsm` block
before `bench.deploy()` reads it, and points `bench.store_root` at
`<root>/<bucket>`, where an object is a file laid out as `FileSystemStorage`
lays it out: the plain reference reads the program's copies from there, and a
fetch cell's set-up stores its segments there as files. The endpoint exits when
its standard input closes, so it cannot outlive the run.

`compared(names)` stops the endpoint, reads its journal and returns the four
numbers that hold the endpoint's record to the configuration's guarantees,
each with limit 0; set-up's requests are in them. 404 and 416 are printed,
not compared.
"""

from __future__ import annotations

import collections
import pathlib
import subprocess
import sys

import s3_endpoint

PREFIX = "storage."


class S3Store:
    def __init__(self, bench) -> None:
        self.bench = bench
        rsm = bench.config["rsm"]
        bucket = rsm[PREFIX + "s3.bucket.name"]
        root = bench.tmp / "s3"
        self.journal_path = bench.tmp / "s3-journal.jsonl"
        (root / bucket).mkdir(parents=True)
        self.process = subprocess.Popen(
            [
                sys.executable, str(bench.here / "s3_endpoint.py"),
                "--root", str(root), "--journal", str(self.journal_path),
                "--access-key", rsm[PREFIX + "aws.access.key.id"],
                "--secret-key", rsm[PREFIX + "aws.secret.access.key"],
            ],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        )
        ready = self.process.stdout.readline()
        if not ready.startswith(s3_endpoint.READY):
            raise bench.harness.refuse(f"the S3 endpoint did not start: {ready!r}")
        self.port = int(ready.rsplit("port=", 1)[1])
        rsm[PREFIX + "s3.endpoint.url"] = f"http://127.0.0.1:{self.port}"
        bench.store_root = root / bucket
        bench.harness.emit({
            "phase": "s3_endpoint", "port": self.port, "pid": self.process.pid,
            "root": str(root), "bucket": bucket,
        })

    def stop(self) -> list[dict]:
        """Ends the endpoint and returns its journal."""
        self.process.stdin.close()
        self.process.wait(timeout=60)
        return s3_endpoint.read_journal(self.journal_path)

    def compared(self, acknowledged_names: list) -> dict:
        """`acknowledged_names`: the `SegmentName`s of the copies the program
        acknowledged in this run (none in a fetch cell)."""
        journal = self.stop()
        not_last = 0
        for name in acknowledged_names:
            prefix = str(name.path(pathlib.PurePosixPath(""), ""))  # ends in "."
            last = s3_endpoint.journal_last_change(journal, prefix)
            not_last += not (
                last is not None and last["op"] == "PutObject" and last["status"] == 200
                and last["key"] == prefix + "rsm-manifest"
            )
        answered = collections.Counter(f"{r['op']} {r['status']}" for r in journal)
        self.bench.harness.emit({
            "check": "s3 journal", "requests": len(journal), "answered": dict(sorted(answered.items())),
            "not_found_404": sum(r["status"] == 404 for r in journal),
            "range_416": sum(r["status"] == 416 for r in journal),
            "copies_held_to_manifest_last": len(acknowledged_names),
        })
        return {
            "multipart_uploads_left_open": {
                "value": s3_endpoint.journal_uploads_left_open(journal), "limit": 0,
            },
            "requests_refused_by_store": {
                "value": s3_endpoint.journal_requests_refused(journal), "limit": 0,
            },
            "manifest_put_not_last": {"value": not_last, "limit": 0},
            "parts_under_minimum": {
                "value": s3_endpoint.journal_parts_under_minimum(journal), "limit": 0,
            },
        }
