"""Device: the program's own device seconds (`DispatchStats.device_seen_ns`, what the device watch's `device.window` spans add up to) over the
profiler's (`busy_s`) in the traced copy: 1.0 is exact, above it is the watch's lateness and the launch's own time, below 0.98 the watch missed work."""
from _idle import busy_seen_ratio as read  # noqa: F401
