"""Share of the traced fetches in which the chip ran nothing (device plane of the profiler trace)."""
from _shared import idle_share as read  # noqa: F401
