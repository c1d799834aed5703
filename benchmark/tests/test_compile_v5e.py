"""Deviceless compiles for the real chip at the cells' real shapes: the
bucketed ALS training program at the rec-ml20m shape and the batch-256
fused top-k at the rec-msd shape. What the v5e compiler refuses here
costs no chip time. Nothing runs, so nothing here is a result or a
time. The topology is described inside a fixture (only the worker that
is given this file loads libtpu); both compiles live in this one file.
"""

import numpy as np
import pytest

from benchmark.harness import data
from benchmark.harness.cell import load_cell


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here: nothing to compile with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_train_program_compiles_and_fits(one_chip):
    import jax

    from predictionio_tpu.ops.als import (
        ALSParams,
        _bucketed_call_args,
        _get_bucketed_jit,
        bucket_ratings_pair,
    )

    cfg = load_cell("rec-ml20m.train").config
    shape, tr = cfg["shape"], cfg["train"]
    st = data.draw_structure(shape)
    vals = np.ones(st.n_events, dtype=np.float32)
    us, its = bucket_ratings_pair(st.rows(), st.cols, vals, st.n_users,
                                  st.n_items)
    params = ALSParams(rank=int(shape["rank"]),
                       num_iterations=int(tr["numIterations"]),
                       lambda_=float(tr["lambda"]), seed=1)
    args, kw = _bucketed_call_args(us, its, params, "fp32", abstract=True)
    kw["solver"] = "lanes"   # what the TPU resolves; the CPU says cho
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        args)
    compiled = _get_bucketed_jit().lower(*args, **kw).compile()
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes
    assert 4e9 < total < 15.5e9   # over a quarter of the chip, and fits


def test_batch256_topk_compiles(one_chip):
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops.als_pallas import fused_gather_score_topk

    shape = load_cell("rec-msd.serve-steady").config["shape"]
    n_users, n_items, rank = (shape["n_users"], shape["n_items"],
                              shape["rank"])
    m_pad = -(-n_items // 512) * 512
    words = -(-m_pad // 32)

    def prog(X, Y, sb, uids):
        Q = jnp.take(X, uids, axis=0).astype(jnp.float32)
        return fused_gather_score_topk(
            Q, Y, k=16, n_items=n_items, mask_seen=True,
            seen_bits=jnp.take(sb, uids, axis=0), interpret=False)

    def sds(s, d):
        return jax.ShapeDtypeStruct(s, d, sharding=one_chip)

    compiled = jax.jit(prog).lower(
        sds((n_users, rank), jnp.bfloat16), sds((m_pad, rank), jnp.bfloat16),
        sds((n_users, words), jnp.int32), sds((256,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 2.9e9   # the store: bitmap + tables
