"""What the readers of the device's idle time and of its split wait share
(PR 37), beside `_spans.py`. With tracing on, a TPU backend's device watch
records a span `device.window` per launched window (the host's upper bound of
the device's time on it), and `Tracer.summary()` then gives every row
`device_idle_s`: the seconds the device ran no window while that name's spans
held it (the launching thread's innermost span, else the innermost on any
thread), with the row `device.unclaimed` for the pieces no span held. The
rows add up to the window less the merged device spans, so a cell's shares
add up to 100.

**Net of the traced run's own pause.** The traced run stops the profiler
inside its window (`Bench.stretch`), and `jax.profiler.stop_trace` writes the
trace for 3.5-7.6 s while the closed loop's client waits: one piece of idle
time that nobody in the program held, as long as all the rest together (my
chip runs, PR 37). It is the harness's, and its length is the trace's, so the
shares leave it out: the longest piece of `device.unclaimed` (the row's
`max_s`), where it is a second or more, which no turn-around of a closed-loop
client is (theirs are under a millisecond at the median). The row itself is
printed as it is.

The table below is the digest by layer. A span name that holds idle seconds
and is in no family is a defect of the table: the whole family of metrics then
reads nothing, and the name is printed, sooner than drop its seconds. Every
reader returns None on a program without the field.
"""

from __future__ import annotations

import sys

from _spans import GIB, _over

#: An unclaimed piece at least this long is the profiler's stop, not traffic.
PROFILER_PAUSE_S = 1.0

#: Family -> the span names it takes: exact names, then prefixes. The codec's
#: two names come before `transform.`, whose other spans are the window's
#: host side.
FAMILIES = (
    ("codec", ("transform.compress", "transform.decompress"), ()),
    ("gateway", (), ("gateway.",)),
    ("store", (), ("rsm.", "storage.", "s3.")),
    ("window_host", (), ("transform.",)),
    ("fetch_tiers", (), ("cache.", "hot.", "fetch.", "chunk.")),
    ("unclaimed", ("device.unclaimed",), ()),
)


def family_of(name: str):
    for family, names, prefixes in FAMILIES:
        if name in names or name.startswith(prefixes):
            return family
    return None


def idle_by_family(observation: dict):
    """({family: idle seconds}, their sum) net of the profiler's pause, or
    None where no row has `device_idle_s`, the device never idled, or a name
    fits no family."""
    spans = observation.get("spans") or {}
    held = {
        name: row["device_idle_s"] for name, row in spans.items() if row.get("device_idle_s")
    }
    if not held:
        return None
    longest = spans.get("device.unclaimed", {}).get("max_s", 0.0)
    if longest >= PROFILER_PAUSE_S:
        held["device.unclaimed"] = max(0.0, held["device.unclaimed"] - longest)
    by_family = dict.fromkeys((family for family, _, _ in FAMILIES), 0.0)
    stray = sorted(name for name in held if family_of(name) is None)
    if stray:
        print(
            f"idle seconds under {', '.join(stray)}: no family of layer_metrics/_idle.py "
            "takes them, so no idle share is reported",
            file=sys.stderr,
        )
        return None
    for name, seconds in held.items():
        by_family[family_of(name)] += seconds
    return by_family, sum(held.values())


def idle_share(observation: dict, family: str):
    """Percent of the device's idle seconds of the window that `family` held."""
    found = idle_by_family(observation)
    if found is None:
        return None
    by_family, total = found
    return 100.0 * by_family[family] / total


def wait_half_seconds(observation: dict, name: str):
    """Seconds of one half of the split `transform.d2h_wait`. A program that
    splits always records `transform.collect`; a window that was ready before
    its wait began leaves no `transform.ready_wait`, which then reads 0."""
    spans = observation.get("spans") or {}
    if "transform.collect" not in spans:
        return None
    return spans.get(name, {}).get("total_s", 0.0)


def wait_half_s_per_gib(observation: dict, name: str):
    return _over(wait_half_seconds(observation, name), observation, "bytes", GIB)


def wait_half_ms_per_fetch(observation: dict, name: str):
    return _over(wait_half_seconds(observation, name), observation, "fetches", 1e3)


def busy_seen_ratio(observation: dict):
    """The program's device seconds over the profiler's, in the traced
    stretch: `DispatchStats.device_seen_ns` (what the `device.window` spans
    add up to) over the union of the device plane's operations. 1.0 is exact;
    above it is the watch's lateness and the launch's own time; below it the
    watch missed device work."""
    stretch = observation.get("stretch") or {}
    seen = (stretch.get("counters") or {}).get("device_seen_ns")
    if seen is None or not stretch.get("busy_s"):
        return None
    return seen / 1e9 / stretch["busy_s"]
