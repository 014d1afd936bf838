#!/usr/bin/env python3
"""An S3-compatible endpoint over a directory, as a process of its own.

    python3 benchmark/s3_endpoint.py --root <dir> --journal <file> \\
        --access-key <id> --secret-key <secret> [--port 0]

It prints `S3_ENDPOINT_READY port=<n>`, serves on 127.0.0.1 until its standard
input closes (so it dies with the run that started it), then writes the journal
and exits. It is the yardstick's: standard library only, importing nothing of
the program. An object `<bucket>/<key>` is the file `<root>/<bucket>/<key>`,
the layout `FileSystemStorage` gives `storage.root`, so the plain reference
reads and writes `<root>/<bucket>` as it does a filesystem store.

What it is true to, because a configuration's guarantees lean on it:

- **SigV4 on every request, from the raw request, payload included**: the
  signature is rebuilt from the request line and headers as they arrived
  (S3's rules: the path as sent, never re-encoded), `x-amz-content-sha256`
  has to be among the signed headers, and the SHA-256 of the body as received
  has to equal it. Anything else is answered 403 and changes nothing. (The
  request's date is not held against a clock.)
- PutObject, GetObject whole or ranged (206 with `Content-Range`; 416 where
  the range starts at or past the end; 404), DeleteObject, DeleteObjects,
  CreateMultipartUpload, UploadPart, CompleteMultipartUpload,
  AbortMultipartUpload. Nothing else (no listing, no versions, no ACLs): 501.
- **Every part but an upload's last is at least 5 MiB**, or Complete answers
  400 `EntityTooSmall`; Complete checks each part's ETag against the one its
  UploadPart was answered with (400 `InvalidPart`).
- **An object is whole or absent**: a Put's body and an upload's parts go to
  files under `<root>/.incoming`, outside every bucket; a Put renames its file
  into place once its hash has been checked, a Complete writes the parts end
  to end into one more file there and renames that. A Complete therefore
  costs one object's worth of local reads and writes while its caller waits.
- Bodies move in 1 MiB blocks through one buffer per connection: no part is
  ever whole in memory. HTTP/1.1 keep-alive; a thread a connection;
  `TCP_NODELAY` is left as Python's `http.server` leaves it, which is off
  (Nagle on), so each reply goes out in one gather write (`sendmsg`: the head
  with the body, or with a large body's first block), never as a small write
  followed by another.

What it is not: no TLS, no network, no replication or durability of a real
store (nothing is fsynced), one machine. An ETag is the first 32 hex digits of
the body's SHA-256 (one hash pass a byte), not its MD5.

The journal is one JSON line a request, in the order the replies were sent:
`op`, `bucket`, `key`, `status`, `bytes` (of the body received, or sent for a
GetObject), `upload_id`, `part`, `t` (monotonic seconds when the reply had
gone out). `read_journal()` and the four `journal_*` reckonings below are
what a generator's check calls.
"""

from __future__ import annotations

import argparse
import hashlib
import hmac
import json
import os
import pathlib
import re
import sys
import threading
import time
import uuid
import xml.etree.ElementTree as ET
from http import HTTPStatus
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qsl, quote, unquote, urlsplit

BLOCK = 1 << 20
MIN_PART_BYTES = 5 << 20
INCOMING = ".incoming"
READY = "S3_ENDPOINT_READY"

_AUTHORIZATION = re.compile(
    r"AWS4-HMAC-SHA256 Credential=([^/]+)/(\d{8})/([^/]+)/([^/]+)/aws4_request,\s*"
    r"SignedHeaders=([^,]+),\s*Signature=([0-9a-f]{64})"
)
_RANGE = re.compile(r"bytes=(\d+)-(\d*)")
_MUST_BE_SIGNED = ("host", "x-amz-content-sha256", "x-amz-date")


class Refused(Exception):
    """A request answered with an S3 error document."""

    def __init__(self, status: int, code: str, message: str = "") -> None:
        super().__init__(f"{status} {code}: {message}")
        self.status, self.code, self.message = status, code, message


def _xml(tag: str, children: dict) -> bytes:
    root = ET.Element(tag)
    for name, text in children.items():
        ET.SubElement(root, name).text = text
    return ET.tostring(root, encoding="utf-8", xml_declaration=True)


class Store:
    """The directory, the uploads in flight and the journal."""

    def __init__(self, root: pathlib.Path, journal: pathlib.Path,
                 access_key: str, secret_key: str) -> None:
        self.root = root
        self.incoming = root / INCOMING
        self.incoming.mkdir(parents=True, exist_ok=True)
        self.access_key, self.secret_key = access_key, secret_key
        self.lock = threading.Lock()
        #: upload id -> {"bucket", "key", "parts": {number: (path, bytes, etag)}}
        self.uploads: dict[str, dict] = {}
        self._journal_path = journal
        self._journal: list[str] = []

    def scratch(self) -> pathlib.Path:
        return self.incoming / uuid.uuid4().hex

    def object_path(self, bucket: str, key: str) -> pathlib.Path:
        parts = [bucket, *key.split("/")]
        if not bucket or not key or any(p in ("", ".", "..") for p in parts) \
                or bucket == INCOMING:
            raise Refused(400, "InvalidArgument", f"bad bucket or key: {bucket}/{key}")
        return self.root.joinpath(*parts)

    def record(self, **line) -> None:
        line["t"] = time.monotonic()
        text = json.dumps(line)
        with self.lock:
            self._journal.append(text)

    def flush_journal(self) -> None:
        with self.lock:
            lines, self._journal = self._journal, []
        with open(self._journal_path, "a") as out:
            out.writelines(line + "\n" for line in lines)


class Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    store: Store

    def setup(self) -> None:
        super().setup()
        self._block = bytearray(BLOCK)  # this connection's one buffer

    def log_message(self, fmt, *args) -> None:  # noqa: A002: a quiet server
        pass

    # ------------------------------------------------------------ the request
    def _parse(self) -> None:
        parts = urlsplit(self.path)
        self.raw_path, self.raw_query = parts.path, parts.query
        segments = parts.path.lstrip("/").split("/", 1)
        self.bucket = unquote(segments[0])
        self.key = unquote(segments[1]) if len(segments) > 1 else ""
        self.query = dict(parse_qsl(parts.query, keep_blank_values=True))
        self.op = self._classify()
        self.upload_id = self.query.get("uploadId")
        number = self.query.get("partNumber", "")
        self.part = int(number) if number.isdigit() else None
        try:
            self.body_left = int(self.headers.get("Content-Length") or 0)
        except ValueError:
            self.body_left = 0
        self.body_bytes = 0
        self._hash = hashlib.sha256()

    def _classify(self) -> str:
        method, query = self.command, self.query
        if method == "GET":
            return "GetObject" if self.key else "Unsupported"
        if method == "PUT":
            return "UploadPart" if "partNumber" in query else "PutObject"
        if method == "DELETE":
            return "AbortMultipartUpload" if "uploadId" in query else "DeleteObject"
        if method == "POST":
            if "delete" in query:
                return "DeleteObjects"
            if "uploads" in query:
                return "CreateMultipartUpload"
            if "uploadId" in query:
                return "CompleteMultipartUpload"
        return "Unsupported"

    def _body_blocks(self):
        """The request body, a view of this connection's buffer at a time,
        hashed as it passes."""
        view = memoryview(self._block)
        while self.body_left:
            n = self.rfile.readinto(view[: min(BLOCK, self.body_left)])
            if not n:
                self.close_connection = True
                raise Refused(400, "IncompleteBody", "the body ended before Content-Length")
            self.body_left -= n
            self.body_bytes += n
            self._hash.update(view[:n])
            yield view[:n]

    def _body_to_file(self, path: pathlib.Path) -> None:
        with open(path, "wb") as out:
            for block in self._body_blocks():
                out.write(block)

    def _body_in_memory(self, limit: int = 8 * BLOCK) -> bytes:
        if self.body_left > limit:
            raise Refused(400, "MaxMessageLengthExceeded", "a document this large is refused")
        return b"".join(bytes(block) for block in self._body_blocks())

    # ----------------------------------------------------------- SigV4, twice
    def _check_signature(self) -> None:
        """The signature over the request as it arrived. An independent
        reconstruction: the canonical request is put together from the raw
        request line and headers, as S3 does it."""
        store = self.store
        match = _AUTHORIZATION.fullmatch((self.headers.get("Authorization") or "").strip())
        if not match:
            raise Refused(403, "AccessDenied", "missing or malformed Authorization")
        access_key, datestamp, region, service, signed_headers, signature = match.groups()
        if access_key != store.access_key:
            raise Refused(403, "InvalidAccessKeyId", access_key)
        names = signed_headers.split(";")
        unsigned = [name for name in _MUST_BE_SIGNED if name not in names]
        if unsigned:
            raise Refused(403, "AccessDenied", f"not signed: {', '.join(unsigned)}")
        pairs = []
        for item in self.raw_query.split("&") if self.raw_query else []:
            name, _, value = item.partition("=")
            pairs.append((unquote(name), unquote(value)))
        canonical_query = "&".join(
            f"{quote(k, safe='-._~')}={quote(v, safe='-._~')}" for k, v in sorted(pairs)
        )
        canonical_headers = "".join(
            f"{name}:{(self.headers.get(name) or '').strip()}\n" for name in names
        )
        canonical_request = "\n".join([
            self.command, self.raw_path or "/", canonical_query, canonical_headers,
            signed_headers, self.headers.get("x-amz-content-sha256", ""),
        ])
        scope = f"{datestamp}/{region}/{service}/aws4_request"
        string_to_sign = "\n".join([
            "AWS4-HMAC-SHA256", self.headers.get("x-amz-date", ""), scope,
            hashlib.sha256(canonical_request.encode()).hexdigest(),
        ])
        key = b"AWS4" + store.secret_key.encode()
        for part in (datestamp, region, service, "aws4_request"):
            key = hmac.new(key, part.encode(), hashlib.sha256).digest()
        expected = hmac.new(key, string_to_sign.encode(), hashlib.sha256).hexdigest()
        if not hmac.compare_digest(expected, signature):
            raise Refused(403, "SignatureDoesNotMatch", "the signature does not match the request")

    def _check_payload_hash(self) -> str:
        """Once the whole body has passed: its SHA-256 against the signed
        header. Returns the digest."""
        digest = self._hash.hexdigest()
        if not hmac.compare_digest(digest, self.headers.get("x-amz-content-sha256", "")):
            raise Refused(403, "XAmzContentSHA256Mismatch", "the body is not the one signed")
        return digest

    # -------------------------------------------------------------- the reply
    def _send(self, buffers: list) -> None:
        """All of `buffers` in as few gather writes as the kernel allows."""
        pending = [memoryview(b) for b in buffers if len(b)]
        while pending:
            sent = self.connection.sendmsg(pending)
            while sent and pending:
                if sent >= len(pending[0]):
                    sent -= len(pending.pop(0))
                else:
                    pending[0] = pending[0][sent:]
                    sent = 0

    def _head(self, status: int, length: int, headers: dict | None = None) -> bytes:
        lines = [f"HTTP/1.1 {status} {HTTPStatus(status).phrase}", f"Content-Length: {length}"]
        lines += [f"{name}: {value}" for name, value in (headers or {}).items()]
        if self.close_connection:
            lines.append("Connection: close")
        return ("\r\n".join(lines) + "\r\n\r\n").encode("latin-1")

    def _reply(self, status: int, body: bytes = b"", headers: dict | None = None,
               sent_bytes: int | None = None) -> None:
        self._send([self._head(status, len(body), headers), body])
        self._journal(status, self.body_bytes if sent_bytes is None else sent_bytes)

    def _journal(self, status: int, n_bytes: int) -> None:
        self.store.record(
            op=self.op, bucket=self.bucket, key=self.key, status=status, bytes=n_bytes,
            upload_id=self.upload_id, part=self.part,
        )

    def _handle(self) -> None:
        self._parse()
        try:
            try:
                self._check_signature()
                getattr(self, "_" + self.op)()
            finally:
                # What is left of the body is read, so that the connection
                # stays parseable for the next request.
                for _ in self._body_blocks():
                    pass
        except Refused as refusal:
            self._refuse(refusal)
        except ConnectionError:
            self.close_connection = True  # the client has left
        except OSError as exc:  # the directory's
            self.close_connection = True
            self._refuse(Refused(500, "InternalError", f"{type(exc).__name__}: {exc}"))

    def _refuse(self, refusal: Refused) -> None:
        self._reply(refusal.status, _xml("Error", {
            "Code": refusal.code, "Message": refusal.message,
        }), {"Content-Type": "application/xml"})

    do_GET = do_PUT = do_POST = do_DELETE = _handle

    # ---------------------------------------------------------- the operations
    def _Unsupported(self) -> None:
        raise Refused(501, "NotImplemented", f"{self.command} {self.path}")

    def _PutObject(self) -> None:
        store = self.store
        target = store.object_path(self.bucket, self.key)
        scratch = store.scratch()
        try:
            self._body_to_file(scratch)
            digest = self._check_payload_hash()
            target.parent.mkdir(parents=True, exist_ok=True)
            os.replace(scratch, target)
        finally:
            scratch.unlink(missing_ok=True)
        self._reply(200, headers={"ETag": f'"{digest[:32]}"'})

    def _GetObject(self) -> None:
        self._check_payload_hash()  # of the empty body
        target = self.store.object_path(self.bucket, self.key)
        try:
            source = open(target, "rb", buffering=0)
        except (FileNotFoundError, IsADirectoryError, NotADirectoryError):
            raise Refused(404, "NoSuchKey", self.key) from None
        with source:
            size = os.fstat(source.fileno()).st_size
            status, start, end, headers = 200, 0, size - 1, {}
            asked = self.headers.get("Range")
            if asked is not None:
                match = _RANGE.fullmatch(asked.strip())
                if not match:
                    raise Refused(400, "InvalidArgument", asked)
                start = int(match.group(1))
                if start >= size:
                    raise Refused(416, "InvalidRange", f"{asked} of {size} bytes")
                end = min(int(match.group(2)), size - 1) if match.group(2) else size - 1
                status, headers = 206, {"Content-Range": f"bytes {start}-{end}/{size}"}
            length = end - start + 1
            source.seek(start)
            head, left = self._head(status, length, headers), length
            view = memoryview(self._block)
            while left or head:
                n = source.readinto(view[: min(BLOCK, left)]) if left else 0
                if left and not n:
                    self.close_connection = True  # the file shrank under us
                    break
                self._send([head, view[:n]])
                head, left = b"", left - n
        self._journal(status, length - left)

    def _DeleteObject(self) -> None:
        self._check_payload_hash()
        self.store.object_path(self.bucket, self.key).unlink(missing_ok=True)
        self._reply(204)

    def _DeleteObjects(self) -> None:
        document = self._body_in_memory()
        self._check_payload_hash()
        try:
            keys = [obj.findtext("Key") or "" for obj in ET.fromstring(document).findall("Object")]
        except ET.ParseError as exc:
            raise Refused(400, "MalformedXML", str(exc)) from None
        for key in keys:
            self.store.object_path(self.bucket, key).unlink(missing_ok=True)
        self._reply(200, _xml("DeleteResult", {}), {"Content-Type": "application/xml"})

    def _CreateMultipartUpload(self) -> None:
        self._check_payload_hash()
        store = self.store
        store.object_path(self.bucket, self.key)  # refuses a bad key now
        self.upload_id = uuid.uuid4().hex
        with store.lock:
            store.uploads[self.upload_id] = {"bucket": self.bucket, "key": self.key, "parts": {}}
        self._reply(200, _xml("InitiateMultipartUploadResult", {
            "Bucket": self.bucket, "Key": self.key, "UploadId": self.upload_id,
        }), {"Content-Type": "application/xml"})

    def _upload(self) -> dict:
        with self.store.lock:
            upload = self.store.uploads.get(self.upload_id)
        if upload is None or (upload["bucket"], upload["key"]) != (self.bucket, self.key):
            raise Refused(404, "NoSuchUpload", str(self.upload_id))
        return upload

    def _UploadPart(self) -> None:
        store = self.store
        upload = self._upload()
        if self.part is None or not 1 <= self.part <= 10000:
            raise Refused(400, "InvalidArgument", f"part number {self.query.get('partNumber')}")
        scratch = store.scratch()
        try:
            self._body_to_file(scratch)
            digest = self._check_payload_hash()
        except BaseException:
            scratch.unlink(missing_ok=True)
            raise
        etag = f'"{digest[:32]}"'
        with store.lock:
            replaced = upload["parts"].get(self.part)
            upload["parts"][self.part] = (scratch, self.body_bytes, etag)
        if replaced is not None:
            replaced[0].unlink(missing_ok=True)
        self._reply(200, headers={"ETag": etag})

    def _CompleteMultipartUpload(self) -> None:
        store = self.store
        document = self._body_in_memory()
        self._check_payload_hash()
        upload = self._upload()
        try:
            listed = [
                (int(part.findtext("PartNumber") or 0), part.findtext("ETag") or "")
                for part in ET.fromstring(document).findall("Part")
            ]
        except (ET.ParseError, ValueError) as exc:
            raise Refused(400, "MalformedXML", str(exc)) from None
        with store.lock:
            held = dict(upload["parts"])
        if not listed:
            raise Refused(400, "MalformedXML", "no parts listed")
        if [n for n, _ in listed] != sorted({n for n, _ in listed}):
            raise Refused(400, "InvalidPartOrder", "part numbers must ascend")
        for number, etag in listed:
            if number not in held or held[number][2] != etag:
                raise Refused(400, "InvalidPart", f"part {number}: unknown, or another ETag")
        for number, _ in listed[:-1]:
            if held[number][1] < MIN_PART_BYTES:
                raise Refused(
                    400, "EntityTooSmall",
                    f"part {number} has {held[number][1]} bytes; only the last may be under "
                    f"{MIN_PART_BYTES}",
                )
        target = store.object_path(self.bucket, self.key)
        scratch = store.scratch()
        view = memoryview(self._block)
        try:
            with open(scratch, "wb") as out:
                for number, _ in listed:
                    with open(held[number][0], "rb", buffering=0) as part:
                        while n := part.readinto(view):
                            out.write(view[:n])
            target.parent.mkdir(parents=True, exist_ok=True)
            os.replace(scratch, target)
        finally:
            scratch.unlink(missing_ok=True)
        self._forget_upload(upload)
        self._reply(200, _xml("CompleteMultipartUploadResult", {
            "Bucket": self.bucket, "Key": self.key,
        }), {"Content-Type": "application/xml"})

    def _AbortMultipartUpload(self) -> None:
        self._check_payload_hash()
        with self.store.lock:
            upload = self.store.uploads.get(self.upload_id)
        if upload is not None:
            self._forget_upload(upload)
        self._reply(204)

    def _forget_upload(self, upload: dict) -> None:
        with self.store.lock:
            self.store.uploads.pop(self.upload_id, None)
            parts, upload["parts"] = upload["parts"], {}
        for path, _, _ in parts.values():
            path.unlink(missing_ok=True)


# ------------------------------------------------- what a check reads from it
def read_journal(path) -> list[dict]:
    with open(path) as lines:
        return [json.loads(line) for line in lines if line.strip()]


def journal_uploads_left_open(journal: list[dict]) -> int:
    """Multipart uploads created (200) and neither completed (200) nor
    aborted (204)."""
    opened = {r["upload_id"] for r in journal
              if r["op"] == "CreateMultipartUpload" and r["status"] == 200}
    closed = {r["upload_id"] for r in journal
              if (r["op"], r["status"]) in (("CompleteMultipartUpload", 200),
                                            ("AbortMultipartUpload", 204))}
    return len(opened - closed)


def journal_requests_refused(journal: list[dict]) -> int:
    """Every 400, 403 and 5xx the endpoint answered."""
    return sum(r["status"] in (400, 403) or r["status"] >= 500 for r in journal)


def journal_parts_under_minimum(journal: list[dict]) -> int:
    """Parts under 5 MiB that are not the last of a completed upload."""
    parts: dict[str, dict[int, int]] = {}
    for r in journal:
        if r["op"] == "UploadPart" and r["status"] == 200:
            parts.setdefault(r["upload_id"], {})[r["part"]] = r["bytes"]
    completed = {r["upload_id"] for r in journal
                 if r["op"] == "CompleteMultipartUpload" and r["status"] == 200}
    short = 0
    for upload_id in completed:
        sizes = parts.get(upload_id, {})
        short += sum(sizes[n] < MIN_PART_BYTES for n in sorted(sizes)[:-1])
    return short


_CHANGES_THE_STORE = (
    "PutObject", "UploadPart", "CreateMultipartUpload", "CompleteMultipartUpload",
    "AbortMultipartUpload", "DeleteObject", "DeleteObjects",
)


def journal_last_change(journal: list[dict], key_prefix: str) -> dict | None:
    """The last request that changed the store under `key_prefix` (a
    segment's objects share theirs), refused ones included."""
    last = None
    for r in journal:
        if r["op"] in _CHANGES_THE_STORE and r["key"].startswith(key_prefix):
            last = r
    return last


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--root", required=True, type=pathlib.Path)
    parser.add_argument("--journal", required=True, type=pathlib.Path)
    parser.add_argument("--access-key", required=True)
    parser.add_argument("--secret-key", required=True)
    parser.add_argument("--port", type=int, default=0)
    args = parser.parse_args(argv)

    store = Store(args.root, args.journal, args.access_key, args.secret_key)
    handler = type("BoundHandler", (Handler,), {"store": store})
    server = ThreadingHTTPServer(("127.0.0.1", args.port), handler)
    server.daemon_threads = True
    thread = threading.Thread(target=server.serve_forever, name="s3-endpoint", daemon=True)
    thread.start()
    print(f"{READY} port={server.server_address[1]}", flush=True)
    try:
        sys.stdin.buffer.read()  # until whoever started this closes it, or dies
    except KeyboardInterrupt:
        pass
    server.shutdown()
    server.server_close()
    store.flush_journal()
    return 0


if __name__ == "__main__":
    sys.exit(main())
