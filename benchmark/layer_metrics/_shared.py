"""What the `.copy` / `.fetch` twins share. Each reader takes the run's
observation (`run.py` `Bench.observation`) and returns a number, or None
where there is nothing to read, and the metric is then left out of the line.

    observation["stretch"]   the traced stretch: window_s, busy_s (device
                             plane of the profiler trace), counters (deltas)
    observation["spans"]     Tracer.summary() of the whole window
    observation["counters"]  deltas of the window of what `counters/*.py` read
    observation["window"]    seconds, bytes and operations of the window
    observation["peaks"]     the device's row of peaks.json
"""

from __future__ import annotations

GIB = 1 << 30


def idle_share(observation: dict):
    """Percent of the traced stretch in which no operation ran on the chip."""
    stretch = observation.get("stretch")
    if not stretch or not stretch.get("window_s"):
        return None
    return 100.0 * (1.0 - stretch["busy_s"] / stretch["window_s"])


def hbm_bound_seconds(payload_bytes: int, peaks: dict) -> float:
    """The least time the chip could take over `payload_bytes` of AES-GCM:
    each byte read from HBM once and written once. This is the HBM bound and
    the only one that rests on a published peak; whatever implements the
    cipher has to move these bytes, so the share stays comparable when a
    kernel is replaced, and it reads low while the VPU work sets the pace."""
    return 2 * payload_bytes / peaks["hbm_bytes_per_s"]


def gcm_roofline_share(observation: dict):
    """Percent: the HBM bound of the payload launched in the traced stretch
    over the seconds the device was busy in it."""
    stretch = observation.get("stretch")
    if not stretch or not stretch.get("busy_s"):
        return None
    payload = stretch["counters"]["bytes_in"]
    if not payload:
        return None
    return 100.0 * hbm_bound_seconds(payload, observation["peaks"]) / stretch["busy_s"]


def span_seconds_per_gib(observation: dict, names: tuple):
    """Busy seconds of the named spans over the window, per GiB the window
    moved. Spans of pipelined windows overlap, so these may sum past the wall
    time."""
    spans = observation.get("spans") or {}
    present = [spans[name]["total_s"] for name in names if name in spans]
    moved = observation["window"].get("bytes")
    if not present or not moved:
        return None
    return sum(present) / (moved / GIB)


def span_mean_ms(observation: dict, name: str):
    row = (observation.get("spans") or {}).get(name)
    return None if row is None else 1e3 * row["avg_s"]


def per(observation: dict, counter: str, per_counter_or_window: str):
    """One count of the window over another; the divisor is a counter or a
    field of the window (its operations)."""
    counters = observation["counters"]
    divisor = counters.get(per_counter_or_window) or observation["window"].get(
        per_counter_or_window
    )
    return counters[counter] / divisor if divisor else None


def chunk_reads(observation: dict) -> int:
    """Chunk reads that reached the device hot tier in the window."""
    counters = observation["counters"]
    return counters["hot_hits"] + counters["hot_misses"]
