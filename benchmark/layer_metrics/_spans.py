"""What the readers of the program's inner spans and exact counters share
(PR 27), beside `_shared.py`, which documents the observation. A span row of
`observation["spans"]` has `total_s` (busy seconds of the name's spans over
the window) and `self_s` (the same less what their child spans cover). Every
reader returns None where the program has no such span, row field or counter,
and the metric is then left out of the line.
"""

from __future__ import annotations

GIB = 1 << 30


def span_seconds(observation: dict, names: tuple, field: str = "total_s"):
    """Summed `field` of the named spans over the window; None unless every
    name is there with that field."""
    spans = observation.get("spans") or {}
    rows = [spans.get(name) for name in names]
    if any(row is None or field not in row for row in rows):
        return None
    return sum(row[field] for row in rows)


def counted(observation: dict, counter: str):
    return (observation.get("counters") or {}).get(counter)


def _over(value, observation: dict, divisor: str, scale: float = 1.0):
    per = observation["window"].get(divisor)
    return None if value is None or not per else scale * value / per


def seconds_per_gib(observation: dict, names: tuple, field: str = "total_s"):
    """Seconds of the named spans per GiB the window moved. Spans of
    pipelined windows overlap, so layers may sum past the wall time."""
    return _over(span_seconds(observation, names, field), observation, "bytes", GIB)


def ms_per_fetch(observation: dict, names: tuple, field: str = "total_s"):
    """Milliseconds of the named spans per answered fetch of the window:
    over all fetches, not per span, so that layers add up to a fetch."""
    return _over(span_seconds(observation, names, field), observation, "fetches", 1e3)


def counter_seconds_per_gib(observation: dict, counter: str):
    return _over(counted(observation, counter), observation, "bytes", GIB)


def counter_ms_per_fetch(observation: dict, counter: str):
    return _over(counted(observation, counter), observation, "fetches", 1e3)


def counter_per(observation: dict, counter: str, operations: str):
    return _over(counted(observation, counter), observation, operations)
