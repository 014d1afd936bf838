"""Transform backend: encrypt streams in flight at an encrypt window's launch, over the window's launches
(`DispatchStats.encrypt_stream_launch_sum` over `.dispatches`, exact; a copy cell launches encrypt windows only, each
`transform_windows` call with encryption a stream from its first dispatch to its last finish): 1.0 where one copy is in
flight at a time, up to the number of concurrent copies where their windows share the device; nothing where the program has
no such count."""
from _spans import counted


def read(observation):
    streams, launches = counted(observation, "encrypt_stream_launch_sum"), counted(observation, "dispatches")
    return streams / launches if streams is not None and launches else None
