"""Subprocess body for the multi-host test: one of K host processes.

Launched by tests/test_distributed.py with
``XLA_FLAGS=--xla_force_host_platform_device_count=D`` so each process
contributes D virtual CPU devices; jax.distributed connects them over a
localhost coordinator — the real DCN control-plane code path, minus the
network. Trains the sharded ALS on a fixed tiny problem and prints the
factor checksum for the parent to compare with the single-process run.
"""

import json
import sys


N_USERS, N_ITEMS = 16, 12


def raw_triples():
    """The shared tiny rating triples — ONE definition for workers and
    the parent test's single-process reference, so they can't drift."""
    import numpy as np

    rng = np.random.default_rng(0)
    nnz = 96
    rows = rng.integers(0, N_USERS, nnz)
    cols = rng.integers(0, N_ITEMS, nnz)
    vals = rng.random(nnz).astype(np.float32) + 0.5
    return rows, cols, vals


def make_problem():
    from predictionio_tpu.ops.als import ALSParams, bucket_ratings_pair

    user_side, item_side = bucket_ratings_pair(*raw_triples(), N_USERS,
                                               N_ITEMS)
    return user_side, item_side, ALSParams(rank=4, num_iterations=3,
                                           seed=0)


def main() -> None:
    coordinator, num_hosts, process_id = (
        sys.argv[1], int(sys.argv[2]), int(sys.argv[3]))

    import numpy as np

    from predictionio_tpu.parallel import distributed
    from predictionio_tpu.parallel.als_sharding import (
        train_als_bucketed_sharded,
    )

    cfg = distributed.DistributedConfig(
        coordinator=coordinator, num_hosts=num_hosts, process_id=process_id)
    assert distributed.initialize(cfg) is True
    assert distributed.process_count() == num_hosts
    assert distributed.process_index() == process_id

    user_side, item_side, params = make_problem()

    # each host contributes its row block of every bucket table
    mesh = distributed.host_aware_mesh()
    X, Y = train_als_bucketed_sharded(user_side, item_side, params, mesh)

    print(json.dumps({
        "process_id": process_id,
        "devices": len(mesh.devices.ravel()),
        "x_sum": float(np.abs(X).sum()),
        "y_sum": float(np.abs(Y).sum()),
        "x_row0": [float(v) for v in X[0]],
    }), flush=True)
    distributed.shutdown()


if __name__ == "__main__":
    main()
