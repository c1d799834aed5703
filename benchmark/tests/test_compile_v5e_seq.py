"""Deviceless compiles for the real chip of the sequence cell's two
programs at the PUBLISHED widths: the optimizer step (8 rows x 4,096
in microbatches of 2: the grouped-matmul and blocked-attention
kernels, forward and backward, Adam over 625.6M parameters) and the
encode call. Their temporaries are read, so an out-of-memory is found
here and not on the chip. Nothing runs: no result, no time.

The program picks its kernels from ``jax.default_backend()``, which is
the CPU here; the test says ``tpu`` for the length of the trace, as
the guide has a test steer such code, so that what is compiled is what
the chip will run. The topology is described inside a fixture."""

import numpy as np
import pytest

from benchmark.harness.cell import load_cell
from benchmark.models import sequentialrec


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


@pytest.fixture()
def as_tpu(monkeypatch):
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _abstract(one_chip, params):
    import jax

    from predictionio_tpu.ops import seqrec

    shapes = jax.eval_shape(
        lambda: seqrec.init_theta_device(41140, params))
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        shapes)


def test_step_and_encode_compile_and_fit(one_chip, as_tpu):
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import seqrec

    params = sequentialrec.seqrec_params(load_cell("seqrec-olmoe-msd.train")
                                         .config, seed=1)
    spec = seqrec.block_spec(params)
    theta = _abstract(one_chip, params)
    n_params = sum(int(np.prod(a.shape))
                   for a in jax.tree_util.tree_leaves(theta))
    assert n_params == 625_616_896

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    L = params.max_seq_len
    batch = (params.batch_size // params.micro_rows, params.micro_rows, L)
    state = (theta, theta, theta, sds((), jnp.float32))
    step = seqrec._train_step_jit(spec, params.learning_rate, 0.0).lower(
        state, *(sds(batch, jnp.int32),) * 3,
        sds((params.n_negatives,), jnp.int32)).compile()
    text = step.as_text()
    # gmm x3 forward, x3 for the rows' gradient, tgmm x3, splash
    # forward and its fused backward
    assert text.count("tpu_custom_call") >= 11
    mem = step.memory_analysis()
    # parameters and both moments: 12 bytes a parameter, donated
    assert mem.argument_size_in_bytes == pytest.approx(12 * n_params,
                                                       rel=0.001)
    assert mem.alias_size_in_bytes >= 12 * n_params
    # the gradients (4 bytes a parameter) and the activations of a
    # microbatch: the whole stays under the chip's 16.9 GB limit
    assert 4 * n_params < mem.temp_size_in_bytes < 9.0e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16.0e9

    rows = (params.encode_rows, L)
    low = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda t: seqrec.low_precision_copies(t, spec),
                       theta))
    encode = seqrec._encode_into_jit(spec).lower(
        theta, low, sds((65536, params.rank), jnp.float32),
        *(sds(rows, jnp.int32),) * 3, sds((768,), jnp.int32),
        sds((768,), jnp.int32)).compile()
    assert encode.as_text().count("tpu_custom_call") >= 4
    mem = encode.memory_analysis()
    assert mem.temp_size_in_bytes < 3.0e9
