"""From a profiler trace (`.xplane.pb`) to device busy seconds and the device
operations that took most time.

On a v5e the trace holds one plane per chip, `/device:TPU:<n>`, whose line
`XLA Ops` has one event per operation the chip ran. Busy time is the union of
that line's events, so operations that overlap count once. With only GCM window
programs on the device, busy time is window-program time, and no operation's
name is matched.
"""

from __future__ import annotations

import collections
import pathlib

TOP = 10


def is_device_line(plane: str, line: str) -> bool:
    return plane.startswith("/device:TPU:") and line == "XLA Ops"


def op_name(event_name: str) -> str:
    """`%fusion.3` of `%fusion.3 = u8[...] fusion(...)`: the trace names an
    operation by its whole HLO line."""
    return event_name.split(" = ", 1)[0][:80]


def merge(intervals: list) -> list:
    """Sorted, disjoint intervals covering the same points."""
    merged: list = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def busy_seconds(intervals: list) -> float:
    return sum(end - start for start, end in merge(intervals))


def newest_xplane(trace_dir: pathlib.Path) -> pathlib.Path:
    files = sorted(pathlib.Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def reduce(trace_dir: pathlib.Path, window_s: float) -> dict:
    """`busy_s` averaged over the chips that the trace holds, and the device
    operations that took most time; seconds throughout."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(str(newest_xplane(trace_dir)))
    per_chip, by_name = [], collections.defaultdict(float)
    for plane in profile.planes:
        for line in plane.lines:
            if not is_device_line(plane.name, line.name):
                continue
            intervals = []
            for event in line.events:
                start = event.start_ns * 1e-9
                intervals.append((start, start + event.duration_ns * 1e-9))
                by_name[op_name(event.name)] += event.duration_ns * 1e-9
            per_chip.append(merge(intervals))
    busy_s = sum(busy_seconds(chip) for chip in per_chip) / len(per_chip) if per_chip else 0.0
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "chips_in_trace": len(per_chip),
        "busy_intervals": sum(len(chip) for chip in per_chip),
        "device_ops": [
            [name, seconds]
            for name, seconds in sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]
        ],
    }
