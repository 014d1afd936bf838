"""h2d, launch, d2h: the encrypt windows already launched and not yet finished when a window is launched, over the window's
launches (`DispatchStats.launch_queue_sum` over `.dispatches`, exact): the device queue a window joins, up to
`pipeline_depth` (3) a stream, so ~0.75 for one copy's four windows and four index rows and up to ten times that under ten
concurrent copies; nothing where the program has no such count."""
from _spans import counted


def read(observation):
    queued, launches = counted(observation, "launch_queue_sum"), counted(observation, "dispatches")
    return queued / launches if queued is not None and launches else None
