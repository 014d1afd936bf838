#!/usr/bin/env python3
"""One profiler trace read for what the benchmark's numbers leave open: are
the program's spans and the device plane on one clock, what was the host doing
while the chip idled, and which stage of the GCM window program took the
device's time.

    python tools/profile_report.py --mode copy|fetch [--seed N] [--operations K]

`main` serves a few warmed copies, or a lagging reader's catch-up fetches, of
the `kip405-aes` deployment (chip_smoke.py's pieces, the benchmark's segment
size) in this process under ONE `jax.profiler` session with `tracing.enabled`,
and prints the report as one JSON line, last. TPU only, like the smoke.

An enabled `Tracer` opens a `jax.profiler.TraceAnnotation` for every span
(utils/tracing.py), so the session's `.xplane.pb` holds the program's spans on
the host plane beside the device plane's `XLA Ops` line, on the profiler's
clock. `reduce(xplane_path)` reads that file and nothing else:

- **clock_check**: a device program cannot start before the host began to
  launch it, nor end after the host was handed its result. With the n
  `transform.launch` annotations sorted by start and the device's window
  programs sorted too, the k-th launch from the END must start at or before
  the k-th program from the end starts; with the `transform.d2h_wait`
  annotations sorted by end, the k-th wait must end at or after the k-th
  program ends. The programs are the events of the device plane's
  `XLA Modules` line (else the bursts of its `XLA Ops` line, operations no
  more than 1 ms apart) that are named for the window path or hold an
  operation of a `gcm.*` scope: one that no window launched would only make
  either test more lenient, and where the trace tells neither every one is
  taken. A
  pair that fails is a violation, `worst_skew_us` its largest shortfall, and
  the two `*_slack_us_min` bound from either side the offset between the
  clocks that the check could not have seen.
- **idle_gaps**: stretches of the traced window longer than 1 ms with no
  device operation, cut at the program's span edges, each piece put down to
  the innermost program span that covers it on the thread that launched the
  program which ended the gap, else on any host thread, summed by span name,
  with the share that no span covers: `utils/tracing.py` `label_gaps`, the
  rule by which the program itself fills `device_idle_s` from its
  `device.window` spans. Labelled only where the clock check found no
  violation. `main` puts the program's own table of the same run beside it
  (`program_by_span_s`).
- **ready_lateness_us**: how late the device watch's stamps are. The k-th
  `device.ready` annotation less the end of the k-th window program on the
  device plane, both sorted: the error of every `device.window` span's end.
- **device_s_by_scope**: device seconds by `gcm.*` named scope of the window
  program (ops/gcm.py), read from the op name that the profiler keeps with
  each operation's metadata.
"""

from __future__ import annotations

import argparse
import bisect
import collections
import http.client
import json
import pathlib
import re
import shutil
import sys
import tempfile
import time

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

from tieredstorage_tpu.utils.tracing import (  # noqa: E402
    DEVICE_READY, _percentile, label_gaps, merge,
)

#: A device gap shorter than this is not reported, and a burst of device work
#: is operations no further apart: a window program's operations follow each
#: other within microseconds, two windows' programs never do.
GAP_NS = 1_000_000
#: Span names of the program: `<layer>.<stage>` as utils/tracing.py's callers
#: name them; every other host event in a trace is the runtime's own.
PROGRAM_SPAN = re.compile(
    r"(gateway|rsm|storage|transform|hot|chunk|fetch|readahead|admission|client)\.[\w.:-]+"
)
#: A named scope of the window program inside an op name such as
#: `jit(_packed_fixed_impl)/jit(_gcm_process_batch)/gcm.ctr/shift_left:` (and
#: not the `ops/gcm.py:320` of a source line).
SCOPE = re.compile(r"(?<=/)gcm\.[a-z_]+(?=/)")
#: The window programs by the name of what was jitted (ops/gcm.py
#: `_packed_fixed_impl`, `_packed_varlen_impl`), as the `XLA Modules` line has
#: it. The scopes alone do not find them all: op metadata is not in the
#: compile-cache key, so an executable that another checkout compiled first
#: comes back from the cache with that checkout's op names.
WINDOW_PROGRAM = re.compile(r"jit__packed_\w+_impl")


def is_device_line(plane: str, line: str) -> bool:
    return plane.startswith("/device:TPU:") and line == "XLA Ops"


def is_program_line(plane: str, line: str) -> bool:
    """One event per program the chip ran, where the trace has the line."""
    return plane.startswith("/device:TPU:") and line == "XLA Modules"


def is_host_plane(plane: str) -> bool:
    return plane == "/host:CPU"


def _varint(buf, at: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: a varint as an int, a
    length-delimited field as a view of its bytes."""
    at = 0
    while at < len(buf):
        key, at = _varint(buf, at)
        wire = key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        else:  # fixed 64 or 32 bits
            size = 8 if wire == 1 else 4
            value, at = buf[at:at + size], at + size
        yield key >> 3, value


def op_scopes(xplane_path) -> dict:
    """{device plane: {operation's event name: its `gcm.*` scope}}. The
    profiler keeps an operation's op name (`tf_op`, named scopes and all) as
    a statistic of the event's METADATA, which `ProfileData` does not show,
    so the file is read here as xplane.proto lays it out: XSpace.planes = 1;
    XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5 (maps: key = 1,
    value = 2); XEventMetadata.name = 2, .stats = 5; XStatMetadata.name = 2;
    XStat.str_value = 5, .ref_value = 7 (the id of a stat metadata whose name
    is the string)."""
    space = memoryview(pathlib.Path(xplane_path).read_bytes())
    scopes: dict = {}
    for number, plane in _fields(space):
        if number != 1:
            continue
        name, operations, strings = "", [], {}
        for number, value in _fields(plane):
            if number == 2:
                name = bytes(value).decode()
            elif number == 4:
                operations.append(dict(_fields(value)).get(2, b""))
            elif number == 5:
                entry = dict(_fields(value))
                strings[entry.get(1, 0)] = dict(_fields(entry.get(2, b""))).get(2, b"")
        if not name.startswith("/device:"):
            continue
        for metadata in operations:
            op, texts = "", []
            for number, value in _fields(metadata):
                if number == 2:
                    op = bytes(value).decode()
                elif number == 5:
                    stat = dict(_fields(value))
                    texts.append(stat.get(5) or strings.get(stat.get(7), b""))
            for text in texts:
                found = SCOPE.search(bytes(text).decode(errors="replace"))
                if found:
                    scopes.setdefault(name, {})[op] = found.group(0)
                    break
    return scopes


def clock_check(launches: list, waits: list, programs: list) -> dict:
    """`launches` and `waits` are (start, end) of the annotations, `programs`
    of the device's window programs, all in nanoseconds on the trace's clock."""
    program_starts = sorted(start for start, _ in programs)
    program_ends = sorted(end for _, end in programs)
    launch_starts = sorted(start for start, _ in launches)
    wait_ends = sorted(end for _, end in waits)
    # (what the host saw) - (what the device says): negative is impossible.
    margins = {"launch": [], "wait": []}
    for k, start in enumerate(reversed(launch_starts), 1):  # k-th from the end
        if k <= len(program_starts):
            margins["launch"].append(program_starts[-k] - start)
    for k, end in enumerate(wait_ends):
        if k < len(program_ends):
            margins["wait"].append(end - program_ends[k])
    unmatched = (
        len(launch_starts) - len(margins["launch"]) + len(wait_ends) - len(margins["wait"])
    )
    shortfalls = [-m for side in margins.values() for m in side if m < 0]

    def least(side: str):
        sound = [m for m in margins[side] if m >= 0]
        return min(sound) / 1e3 if sound else None

    return {
        "launches": len(launches), "waits": len(waits), "device_programs": len(programs),
        "violations": len(shortfalls) + unmatched,
        "launches_or_waits_with_no_program": unmatched,
        "worst_skew_us": max(shortfalls) / 1e3 if shortfalls else 0.0,
        "launch_slack_us_min": least("launch"), "wait_slack_us_min": least("wait"),
    }


def ready_lateness(readies: list, programs: list) -> dict:
    """Microseconds from a window program's end on the device plane to the
    `device.ready` annotation of the watch that waited for it, k-th to k-th
    of both sorted; counted from the end where the trace holds more of one
    than of the other (a program that ended before the trace began)."""
    stamps = sorted(start for start, _ in readies)
    ends = sorted(end for _, end in programs)
    paired = min(len(stamps), len(ends))
    late = sorted(
        (stamp - end) / 1e3
        for stamp, end in zip(stamps[len(stamps) - paired:], ends[len(ends) - paired:])
    )
    out: dict = {"count": paired, "unpaired": len(stamps) + len(ends) - 2 * paired}
    if late:
        out.update(
            min=late[0], p50=_percentile(late, 0.50), p95=_percentile(late, 0.95), max=late[-1]
        )
    return out


def launcher_of_gap(gaps: list, ran: list, programs: list, launches: list) -> list:
    """`gaps` with the thread that launched the program which ended each:
    the first program to start after the gap began, if a window launched it.
    Launches and window programs pair k-th to k-th from the end, as in the
    clock check; `launches` are (start, thread)."""
    starts = sorted(start for start, _ in programs)
    threads = [thread for _, thread in sorted(launches)]
    paired = min(len(starts), len(threads))
    launched_by = dict(zip(starts[len(starts) - paired:], threads[len(threads) - paired:]))
    ran_starts = sorted(run[0] for run in ran)
    out = []
    for gap_start, gap_end in gaps:
        at = bisect.bisect_left(ran_starts, gap_start)
        ender = ran_starts[at] if at < len(ran_starts) else None
        out.append((gap_start, gap_end, launched_by.get(ender)))
    return out


def reduce(xplane_path) -> dict:
    """The report of one `.xplane.pb`; seconds unless a key says otherwise."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(str(xplane_path))
    scopes = op_scopes(xplane_path)
    device, modules, scoped_starts, spans, readies = [], [], [], [], []
    by_scope: dict = collections.defaultdict(int)
    for plane in profile.planes:
        for thread, line in enumerate(plane.lines):
            if is_device_line(plane.name, line.name):
                for event in line.events:
                    start = int(event.start_ns)
                    device.append((start, start + int(event.duration_ns)))
                    scope = scopes.get(plane.name, {}).get(event.name, "unscoped")
                    by_scope[scope] += int(event.duration_ns)
                    if scope != "unscoped":
                        scoped_starts.append(start)
            elif is_program_line(plane.name, line.name):
                for event in line.events:
                    start = int(event.start_ns)
                    modules.append([start, start + int(event.duration_ns), event.name])
            elif is_host_plane(plane.name):
                for event in line.events:
                    start = int(event.start_ns)
                    if event.name == DEVICE_READY:
                        readies.append((start, start + int(event.duration_ns)))
                    elif PROGRAM_SPAN.fullmatch(event.name):
                        spans.append((start, start + int(event.duration_ns), event.name, thread))
    busy = merge(device)
    bursts = merge(device, bridge=GAP_NS)
    # The programs the chip ran: the trace's own line of them, else the bursts
    # of its operations. A window's is one named so, or with an operation of
    # a `gcm.*` scope in it; where the trace tells neither, every one is taken.
    ran = sorted(modules) or [[*burst, ""] for burst in bursts]
    scoped_starts.sort()
    programs = [
        run[:2] for run in ran
        if WINDOW_PROGRAM.match(run[2])
        or bisect.bisect_left(scoped_starts, run[0]) < bisect.bisect_right(scoped_starts, run[1])
    ] or [run[:2] for run in ran]
    check = clock_check(
        [s[:2] for s in spans if s[2] == "transform.launch"],
        [s[:2] for s in spans if s[2] == "transform.d2h_wait"],
        programs,
    )
    check["programs_from"] = "XLA Modules" if modules else "bursts of XLA Ops"
    check["other_device_programs"] = len(ran) - len(programs)
    edges = [t for start, end, *_ in spans + readies + device for t in (start, end)]
    window = (min(edges), max(edges)) if edges else (0, 0)
    # `bursts` are what gaps of at most GAP_NS bridge: between them, and
    # before the first and after the last, lie the gaps to report.
    bounds = [window[0], *(t for burst in bursts for t in burst), window[1]]
    gaps = [
        (start, end) for start, end in zip(bounds[0::2], bounds[1::2])
        if end - start > GAP_NS
    ]
    idle_ns = sum(end - start for start, end in gaps)
    idle: dict = {"longer_than_ms": GAP_NS / 1e6, "count": len(gaps), "idle_s": idle_ns / 1e9}
    if check["violations"] == 0 and check["launches"]:
        launches = [(s[0], s[3]) for s in spans if s[2] == "transform.launch"]
        labelled = label_gaps(launcher_of_gap(gaps, ran, programs, launches), spans)
        idle["by_span_s"] = {
            name: ns / 1e9
            for name, ns in sorted(labelled["by_span"].items(), key=lambda kv: -kv[1])
        }
        idle["uncovered_share"] = sum(labelled["uncovered"]) / idle_ns if idle_ns else 0.0
    else:
        idle["unlabelled"] = "the clock check did not pass"
    return {
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_s": sum(end - start for start, end in busy) / 1e9,
        "device_ops": len(device),
        "program_spans": len(spans),
        "clock_check": check,
        "idle_gaps": idle,
        "ready_lateness_us": ready_lateness(readies, programs),
        "device_s_by_scope": {
            name: ns / 1e9 for name, ns in sorted(by_scope.items(), key=lambda kv: -kv[1])
        },
    }


# ------------------------------------------------------------------ the run
def fetch_tail(port: int, md, start: int, read_bytes: int) -> bytes:
    """The open-ended `fetchLogSegment(md, start)` as Kafka's
    `RemoteLogManager.read` uses it: read one fetch's bytes, close the stream
    (the benchmark's `catchup_scan`)."""
    from tieredstorage_tpu.sidecar import shimwire

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
    try:
        conn.request(
            "POST", "/v1/fetch",
            body=shimwire.encode_metadata(md) + shimwire.encode_fetch_tail(start, None),
        )
        response = conn.getresponse()
        if response.status != 200:
            raise RuntimeError(f"/v1/fetch answered {response.status}")
        return response.read(read_bytes)
    finally:
        conn.close()


def counters() -> dict:
    """The exact counts a span cannot see from inside, as they stand."""
    from tieredstorage_tpu.ops import gcm
    from tieredstorage_tpu.utils.platforms import program_trace_stats

    return {
        **gcm.context_stats(), **program_trace_stats(),
        "gcm_dispatches": gcm.device_dispatches(),
    }


def newest_xplane(trace_dir: pathlib.Path) -> pathlib.Path:
    files = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--mode", choices=("copy", "fetch"), required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--operations", type=int, default=None,
                        help="copies (default 3) or fetches (default 120) under the profiler")
    parser.add_argument("--segment-bytes", type=int, default=(256 << 20) - 300_000,
                        help="the benchmark's cut of log.segment.bytes")
    parser.add_argument("--chunk-bytes", type=int, default=4 << 20)
    parser.add_argument("--read-bytes", type=int, default=1 << 20)
    parser.add_argument("--keep-trace", type=pathlib.Path, default=None,
                        help="copy the session's .xplane.pb here, to be read again")
    args = parser.parse_args(argv)

    sys.path.insert(0, str(REPO_ROOT))
    import chip_smoke as smoke

    smoke.refuse_kernel_switches()
    device = smoke.require_tpu()

    import jax

    from tieredstorage_tpu.utils.platforms import enable_compile_cache

    enable_compile_cache()
    sizes = smoke.Sizes(chunk_bytes=args.chunk_bytes, segment_bytes=args.segment_bytes)
    segment = smoke.make_segment(args.seed, sizes.segment_bytes)
    indexes = smoke.make_indexes(args.seed, sizes.segment_bytes)
    operations = args.operations or (3 if args.mode == "copy" else 120)
    with tempfile.TemporaryDirectory(prefix="profile-report-") as tmp:
        root = pathlib.Path(tmp)
        configs = smoke.tpu_configs(
            smoke.base_configs(root, sizes.chunk_bytes), sizes, compression=False
        )
        configs.update({"tracing.enabled": True, "tracing.max.spans": 400_000})
        deployment = smoke.Deployment(configs)
        try:
            warm = smoke.segment_metadata(args.seed, 0, sizes.segment_bytes)
            deployment.copy(warm, segment, indexes)  # every window shape, a store to read
            # a catch-up reader's steps, as the benchmark's `catchup_scan` takes
            # them, short of the segment's ragged last chunk
            step = 254 * 4096
            positions = [
                (i * step) % (sizes.segment_bytes - 2 * sizes.chunk_bytes)
                for i in range(operations + 8)
            ]
            if args.mode == "fetch":
                for position in positions[:8]:  # the one-row program, the hot tier's
                    fetch_tail(deployment.gateway.port, warm, position, args.read_bytes)
            time.sleep(0.5)  # handlers that stream on after the reader left
            deployment.rsm.tracer.clear()
            before = counters()
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(str(root / "profile"), profiler_options=options)
            begin = time.perf_counter()
            try:
                if args.mode == "copy":
                    for ordinal in range(1, operations + 1):
                        deployment.copy(
                            smoke.segment_metadata(args.seed, ordinal, sizes.segment_bytes),
                            segment, indexes,
                        )
                else:
                    for position in positions[8:]:
                        fetch_tail(deployment.gateway.port, warm, position, args.read_bytes)
                    time.sleep(0.5)
            finally:
                seconds = time.perf_counter() - begin
                jax.profiler.stop_trace()
            watch = deployment.rsm.transform_backend.device_watch
            if watch is not None:
                watch.settle()
            summary = deployment.rsm.tracer.summary()
            counted = {name: value - before[name] for name, value in counters().items()}
        finally:
            deployment.close()
        xplane = newest_xplane(root / "profile")
        if args.keep_trace is not None:
            args.keep_trace.parent.mkdir(parents=True, exist_ok=True)
            shutil.copy(xplane, args.keep_trace)
        report = reduce(xplane)
    # The same table as the program makes it from its own `device.window`
    # spans (utils/tracing.py `device_idle`), with no profiler.
    report["idle_gaps"]["program_by_span_s"] = {
        name: row["device_idle_s"]
        for name, row in sorted(summary.items(), key=lambda kv: -kv[1].get("device_idle_s", 0.0))
        if row.get("device_idle_s")
    }
    for name, row in sorted(summary.items()):
        smoke.emit({"span": name, **{k: round(v, 6) for k, v in row.items()}})
    print(json.dumps({
        "mode": args.mode, "seed": args.seed, "operations": operations,
        "profiled_s": seconds, "device": device, "counters": counted, "report": report,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
