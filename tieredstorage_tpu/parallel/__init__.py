"""Multi-chip scale-out: device mesh + sharded batched transforms.

The reference scales concurrent segment uploads with a broker thread pool
(SURVEY.md §2.11); here the analogue is sharding the chunk batch of one or
more segments across a 1-D "data" mesh axis with GSPMD — every kernel in
ops/ is chunk-parallel, so XLA partitions them with zero cross-chip
collectives on the forward path; only the per-chunk size/crc vectors are
gathered back to the host to build the chunk index.
"""

from tieredstorage_tpu.parallel.mesh import (
    MeshPlan,
    data_mesh,
    pad_batch,
    shard_rows,
)

__all__ = ["MeshPlan", "data_mesh", "pad_batch", "shard_rows"]
