"""TPU compute kernels (JAX/XLA) — the MLlib replacement.

Everything here is jit-compiled, static-shaped, and mesh-shardable.
"""

from predictionio_tpu.ops.als import (
    ALSParams, BucketedRatings, train_als_bucketed)

__all__ = ["ALSParams", "BucketedRatings", "train_als_bucketed"]
