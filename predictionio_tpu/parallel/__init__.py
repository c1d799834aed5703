"""Distributed execution: mesh construction + sharded training steps.

The reference's distribution story is Spark RDD partitioning plus MLlib's
block-partitioned ALS shuffles (SURVEY §2.6). The TPU-native answer is a
``jax.sharding.Mesh`` with GSPMD sharding propagation: we annotate input
shardings; XLA inserts the all-gathers/psums over ICI. No NCCL/MPI analog
is needed — collectives are compiled into the program.
"""

from predictionio_tpu.parallel.mesh import data_parallel_mesh, mesh_2d
from predictionio_tpu.parallel.als_sharding import (
    ItemShardLayout,
    contiguous_item_layout,
    density_aware_item_layout,
    train_als_bucketed_sharded,
)
from predictionio_tpu.parallel import distributed  # multi-host runtime
from predictionio_tpu.parallel.distributed import (
    DistributedConfig,
    host_aware_mesh,
)
from predictionio_tpu.ops.attention import (  # sequence parallel
    ring_attention,
    ulysses_attention,
)

__all__ = ["data_parallel_mesh", "mesh_2d",
           "train_als_bucketed_sharded", "ring_attention", "ulysses_attention",
           "distributed", "DistributedConfig", "host_aware_mesh",
           "ItemShardLayout", "density_aware_item_layout",
           "contiguous_item_layout"]
