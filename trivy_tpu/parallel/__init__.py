"""Multi-chip execution: device meshes, sharded kernels, collectives.

The reference's only parallelism is goroutine fan-out bounded by a weighted
semaphore plus optional client/server RPC offload (ref: SURVEY.md §2.9,
pkg/fanal/analyzer/analyzer.go:403-455, pkg/parallel/pipeline.go). The TPU
equivalent lives here: chunk batches shard over the mesh 'data' axis via
jax.sharding / shard_map, reductions ride ICI collectives (psum), and the
host-side feeder plays the role of the reference's worker pipeline.
"""

from trivy_tpu.parallel.mesh import (  # noqa: F401
    batch_sharding,
    get_mesh,
    pad_batch,
)
