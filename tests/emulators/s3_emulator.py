"""Threaded in-process S3-compatible server (LocalStack stand-in).

Implements the object operations the S3 backend uses: PutObject, GetObject
(with Range), DeleteObject, DeleteObjects, and the multipart upload lifecycle.
State lives in dictionaries guarded by a lock; buckets are implicit.
"""

from __future__ import annotations

import hashlib
import hmac
import re
import threading
import time
import uuid
import xml.etree.ElementTree as ET
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, quote, unquote, urlsplit


class S3State:
    def __init__(self) -> None:
        self.objects: dict[tuple[str, str], bytes] = {}
        self.uploads: dict[str, dict[int, bytes]] = {}
        self.upload_keys: dict[str, tuple[str, str]] = {}
        self.lock = threading.Lock()
        # Fault injection queue: (matcher(method, path) -> bool, status, body)
        self.fail_next: list[tuple] = []
        # Delay queue: (matcher(method, path) -> bool, seconds); the request
        # that matches is held that long before it is looked at.
        self.delay_next: list[tuple] = []
        # One record a request answered, in the order of the replies: method,
        # path, status, the monotonic times it was met and answered; a part's
        # number, bytes and SHA-256; a Complete's part numbers as listed.
        self.requests: list[dict] = []
        # (access_key, secret_key) — when set, every request's SigV4
        # signature is verified against an independent reconstruction from
        # the raw wire request (the way real S3 does; LocalStack-style
        # emulators that skip this let signer bugs through undetected).
        self.credentials: tuple[str, str] | None = None


def _xml(tag: str, children: dict[str, str]) -> bytes:
    root = ET.Element(tag)
    for k, v in children.items():
        ET.SubElement(root, k).text = v
    return ET.tostring(root, encoding="utf-8", xml_declaration=True)


def _error_xml(code: str, message: str) -> bytes:
    return _xml("Error", {"Code": code, "Message": message})


class _Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    state: S3State

    def log_message(self, fmt, *args):  # silence
        pass

    # ------------------------------------------------------------ utilities
    def _split(self) -> tuple[str, str, dict[str, list[str]]]:
        parts = urlsplit(self.path)
        segs = parts.path.lstrip("/").split("/", 1)
        bucket = segs[0] if segs else ""
        key = unquote(segs[1]) if len(segs) > 1 else ""
        return bucket, key, parse_qs(parts.query, keep_blank_values=True)

    def _body(self) -> bytes:
        length = int(self.headers.get("Content-Length", "0"))
        return self.rfile.read(length) if length else b""

    def _reply(self, status: int, body: bytes = b"", headers: dict | None = None) -> None:
        with self.state.lock:
            self.state.requests.append({
                "method": self.command, "path": self.path, "status": status,
                "met": self._met, "answered": time.monotonic(), **self._noted,
            })
        self.send_response(status)
        for k, v in (headers or {}).items():
            self.send_header(k, v)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        if body:
            self.wfile.write(body)

    def _maybe_fail(self) -> bool:
        self._met, self._noted = time.monotonic(), {}
        part = re.search(r"partNumber=(\d+)", self.path)
        if part:
            self._noted["part"] = int(part.group(1))
        with self.state.lock:
            held = next((d for d in self.state.delay_next if d[0](self.command, self.path)), None)
            if held is not None:
                self.state.delay_next.remove(held)
        if held is not None:
            time.sleep(held[1])
        with self.state.lock:
            for i, entry in enumerate(self.state.fail_next):
                matcher, status, body = entry[:3]
                headers = entry[3] if len(entry) > 3 else None
                if matcher(self.command, self.path):
                    self.state.fail_next.pop(i)
                    break
            else:
                return False
        self._body()  # drain the request body to keep the connection parseable
        self._reply(status, body, headers)
        return True

    _AUTH_RE = re.compile(
        r"AWS4-HMAC-SHA256 Credential=([^/]+)/(\d{8})/([^/]+)/([^/]+)/aws4_request,\s*"
        r"SignedHeaders=([^,]+),\s*Signature=([0-9a-f]{64})"
    )

    def _verify_sigv4(self) -> bool:
        """Recompute the SigV4 signature from the raw wire request.

        Canonical URI is the request path exactly as received (S3 semantics:
        single-encoded, never re-encoded) — so a client that double-encodes
        its canonical path fails here the same way it fails on real S3."""
        creds = self.state.credentials
        if creds is None:
            return True
        m = self._AUTH_RE.fullmatch(self.headers.get("Authorization", "").strip())
        if not m:
            self._body()
            self._reply(403, _error_xml("AccessDenied", "missing or malformed Authorization"))
            return False
        access_key, datestamp, region, service, signed_headers, signature = m.groups()
        if access_key != creds[0]:
            self._body()
            self._reply(403, _error_xml("InvalidAccessKeyId", access_key))
            return False
        raw_path, _, raw_query = self.path.partition("?")
        pairs = []
        for item in raw_query.split("&") if raw_query else []:
            k, _, v = item.partition("=")
            pairs.append((unquote(k), unquote(v)))
        enc = lambda s: quote(s, safe="-._~")  # noqa: E731
        canonical_query = "&".join(f"{enc(k)}={enc(v)}" for k, v in sorted(pairs))
        names = signed_headers.split(";")
        canonical_headers = "".join(
            f"{n}:{(self.headers.get(n) or '').strip()}\n" for n in names
        )
        payload_hash = self.headers.get("x-amz-content-sha256", "")
        canonical_request = "\n".join(
            [self.command, raw_path or "/", canonical_query,
             canonical_headers, signed_headers, payload_hash]
        )
        scope = f"{datestamp}/{region}/{service}/aws4_request"
        string_to_sign = "\n".join(
            ["AWS4-HMAC-SHA256", self.headers.get("x-amz-date", ""), scope,
             hashlib.sha256(canonical_request.encode("utf-8")).hexdigest()]
        )
        key = b"AWS4" + creds[1].encode("utf-8")
        for part in (datestamp, region, service, "aws4_request"):
            key = hmac.new(key, part.encode("utf-8"), hashlib.sha256).digest()
        expected = hmac.new(key, string_to_sign.encode("utf-8"), hashlib.sha256).hexdigest()
        if not hmac.compare_digest(expected, signature):
            self._body()
            self._reply(
                403,
                _error_xml(
                    "SignatureDoesNotMatch",
                    f"canonical request was:\n{canonical_request}",
                ),
            )
            return False
        return True

    # ------------------------------------------------------------- handlers
    def do_PUT(self) -> None:
        if self._maybe_fail():
            return
        if not self._verify_sigv4():
            return
        bucket, key, query = self._split()
        body = self._body()
        if "partNumber" in query:
            upload_id = query["uploadId"][0]
            part = int(query["partNumber"][0])
            with self.state.lock:
                if upload_id not in self.state.uploads:
                    self._reply(404, _error_xml("NoSuchUpload", upload_id))
                    return
                self.state.uploads[upload_id][part] = body
            self._noted = {"part": part, "bytes": len(body),
                           "sha256": hashlib.sha256(body).hexdigest()}
            etag = f'"{uuid.uuid5(uuid.NAMESPACE_OID, str(hash(body)))}"'
            self._reply(200, headers={"ETag": etag})
            return
        with self.state.lock:
            self.state.objects[(bucket, key)] = body
        self._reply(200, headers={"ETag": '"etag"'})

    def do_GET(self) -> None:
        if self._maybe_fail():
            return
        if not self._verify_sigv4():
            return
        bucket, key, query = self._split()
        if "list-type" in query:
            self._list_objects(bucket, query)
            return
        with self.state.lock:
            data = self.state.objects.get((bucket, key))
        if data is None:
            self._reply(404, _error_xml("NoSuchKey", key))
            return
        range_header = self.headers.get("Range")
        if range_header:
            m = re.fullmatch(r"bytes=(\d+)-(\d*)", range_header.strip())
            if not m:
                self._reply(400, _error_xml("InvalidArgument", range_header))
                return
            start = int(m.group(1))
            end = int(m.group(2)) if m.group(2) else len(data) - 1
            if start >= len(data):
                self._reply(416, _error_xml("InvalidRange", range_header))
                return
            end = min(end, len(data) - 1)
            piece = data[start : end + 1]
            self._reply(
                206,
                piece,
                headers={"Content-Range": f"bytes {start}-{end}/{len(data)}"},
            )
            return
        self._reply(200, data)

    def _list_objects(self, bucket: str, query: dict[str, list[str]]) -> None:
        """ListObjectsV2: lexicographic keys, 1000-key pages, opaque
        continuation tokens (the last key of the previous page)."""
        prefix = query.get("prefix", [""])[0]
        max_keys = min(int(query.get("max-keys", ["1000"])[0]), 1000)
        token = query.get("continuation-token", [""])[0]
        with self.state.lock:
            keys = sorted(
                k for (b, k) in self.state.objects
                if b == bucket and k.startswith(prefix)
            )
        if token:
            keys = [k for k in keys if k > token]
        page, rest = keys[:max_keys], keys[max_keys:]
        root = ET.Element("ListBucketResult")
        ET.SubElement(root, "Name").text = bucket
        ET.SubElement(root, "Prefix").text = prefix
        ET.SubElement(root, "KeyCount").text = str(len(page))
        ET.SubElement(root, "IsTruncated").text = "true" if rest else "false"
        if rest:
            ET.SubElement(root, "NextContinuationToken").text = page[-1]
        for k in page:
            contents = ET.SubElement(root, "Contents")
            ET.SubElement(contents, "Key").text = k
        self._reply(200, ET.tostring(root, encoding="utf-8", xml_declaration=True))

    def do_DELETE(self) -> None:
        if self._maybe_fail():
            return
        if not self._verify_sigv4():
            return
        bucket, key, query = self._split()
        if "uploadId" in query:
            with self.state.lock:
                self.state.uploads.pop(query["uploadId"][0], None)
                self.state.upload_keys.pop(query["uploadId"][0], None)
            self._reply(204)
            return
        with self.state.lock:
            self.state.objects.pop((bucket, key), None)
        self._reply(204)

    def do_POST(self) -> None:
        if self._maybe_fail():
            return
        if not self._verify_sigv4():
            return
        bucket, key, query = self._split()
        # Always drain the body: an undrained body gets parsed as the next
        # request line on the keep-alive connection, corrupting it.
        body = self._body()
        if "uploads" in query:
            upload_id = uuid.uuid4().hex
            with self.state.lock:
                self.state.uploads[upload_id] = {}
                self.state.upload_keys[upload_id] = (bucket, key)
            self._reply(
                200,
                _xml(
                    "InitiateMultipartUploadResult",
                    {"Bucket": bucket, "Key": key, "UploadId": upload_id},
                ),
            )
            return
        if "uploadId" in query:
            upload_id = query["uploadId"][0]
            with self.state.lock:
                parts = self.state.uploads.pop(upload_id, None)
                target = self.state.upload_keys.pop(upload_id, None)
                if parts is None or target is None:
                    self._reply(404, _error_xml("NoSuchUpload", upload_id))
                    return
                listed = [int(p.findtext("PartNumber")) for p in ET.fromstring(body).iter("Part")]
                blob = b"".join(parts[n] for n in listed)
                self.state.objects[target] = blob
            self._noted = {"parts": listed}
            self._reply(
                200,
                _xml("CompleteMultipartUploadResult", {"Bucket": bucket, "Key": key}),
            )
            return
        if "delete" in query:
            root = ET.fromstring(body)
            deleted = []
            with self.state.lock:
                for obj in root.findall("Object"):
                    k = obj.findtext("Key") or ""
                    self.state.objects.pop((bucket, k), None)
                    deleted.append(k)
            self._reply(200, _xml("DeleteResult", {}))
            return
        self._reply(400, _error_xml("NotImplemented", self.path))


class S3Emulator:
    def __init__(self, credentials: tuple[str, str] | None = None) -> None:
        self.state = S3State()
        self.state.credentials = credentials
        handler = type("Handler", (_Handler,), {"state": self.state})
        self.server = ThreadingHTTPServer(("127.0.0.1", 0), handler)
        self.thread = threading.Thread(target=self.server.serve_forever, daemon=True)

    @property
    def endpoint(self) -> str:
        host, port = self.server.server_address[:2]
        return f"http://{host}:{port}"

    def start(self) -> "S3Emulator":
        self.thread.start()
        return self

    def stop(self) -> None:
        self.server.shutdown()
        self.server.server_close()

    def inject_error(
        self,
        status: int,
        code: str = "SlowDown",
        message: str = "injected",
        when=None,
        headers: dict | None = None,
    ) -> None:
        """Fail the next request (matching `when(method, path)` if given);
        `headers` ride the error response (e.g. Retry-After)."""
        matcher = when if when is not None else (lambda method, path: True)
        with self.state.lock:
            self.state.fail_next.append(
                (matcher, status, _error_xml(code, message), headers)
            )

    def delay(self, seconds: float, when) -> None:
        """Hold the next request matching `when(method, path)` for `seconds`
        before it is looked at (so that a later request overtakes it)."""
        with self.state.lock:
            self.state.delay_next.append((when, seconds))
