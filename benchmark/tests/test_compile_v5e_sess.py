"""Deviceless compiles for the real chip of the session cell's two
programs at the PUBLISHED widths: one dispatch of the session lane
(8 queries x 8 new events against sessions of up to 65,536 cached
events) and one prefill chunk (2,048 events). Their temporaries are
read beside what the deployment holds resident, so an out-of-memory is
found here and not on the chip; the pools are donated and written in
place. Nothing runs: no result, no time. ``jax.default_backend`` says
``tpu`` for the length of the trace (the grouped matmuls are the
Pallas kernels there)."""

import numpy as np
import pytest

from benchmark.harness.cell import load_cell
from benchmark.models import sessionrec


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


@pytest.mark.parametrize("S", [16384, 65536])
def test_extend_and_prefill_compile_and_fit(one_chip, as_tpu, S):
    import functools

    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import mla
    from predictionio_tpu.ops.sessions import (
        SESS_BLOCK,
        SESS_EVENTS,
        SESS_MAX_BATCH,
    )

    config = load_cell("seqrec-glm5.sess-extend").config
    params = sessionrec.seqrec_params(config, seed=1)
    spec = mla.glm_spec(params)
    V, D = int(config["vocab_size"]), spec.width
    bf16 = jnp.bfloat16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    theta = {name: sds(shape, bf16 if mla.is_low(name) else jnp.float32)
             for name, shape, _ in mla.theta_shapes(V, spec)}
    n_params = sum(int(np.prod(a.shape)) for a in theta.values())
    assert n_params == pytest.approx(4.73e9, rel=0.005)
    Y = theta.pop("out_emb")
    bs = SESS_BLOCK
    nb = 1 + int(config["session"]["pool_tokens"]) // bs
    lat = tuple(sds((nb, bs, spec.lat_width), bf16)
                for _ in range(spec.n_layers))
    ik = tuple(sds((nb, bs, spec.idx_dim), bf16)
               for _ in range(spec.n_layers))
    pool = 2 * nb * bs * (spec.lat_width + spec.idx_dim) * spec.n_layers
    assert pool == pytest.approx(4.23e9, rel=0.01)
    n_users = int(config["shape"]["n_users"])
    X = sds((n_users, D), bf16)
    seen = sds((n_users, 640), jnp.int32)
    T, B = SESS_EVENTS, SESS_MAX_BATCH
    extend = jax.jit(functools.partial(
        mla.extend_step, spec=spec, kb=128, T=T, S=S, bs=bs, n_items=V,
        mode="bf16", mask_seen=True, audit=bool(params.session_audit)),
        donate_argnums=(1, 2, 3, 4)).lower(
        theta, X, seen, lat, ik, Y,
        sds((B, 3 + 2 * T + S // bs), jnp.int32)).compile()
    # three grouped matmuls an expert layer
    assert extend.as_text().count("tpu_custom_call") >= 15
    mem = extend.memory_analysis()
    resident = 2 * n_params + pool
    assert mem.argument_size_in_bytes == pytest.approx(resident, rel=0.01)
    assert mem.alias_size_in_bytes >= pool      # the pools, in place
    assert mem.temp_size_in_bytes < 1.5e9
    C = spec.idx_topk
    prefill = jax.jit(functools.partial(
        mla.prefill_chunk, spec=spec, C=C, S=S, bs=bs, qb=32),
        donate_argnums=(1, 2, 3)).lower(
        theta, X, lat, ik, sds((3 + 2 * C + S // bs,), jnp.int32)).compile()
    mem = prefill.memory_analysis()
    assert mem.alias_size_in_bytes >= pool
    assert mem.temp_size_in_bytes < 2.0e9
    # resident + the similarity lane's copy of the slice + the largest
    # program's scratch stay inside the chip's 16.9 GB
    assert resident + 0.24e9 + mem.temp_size_in_bytes < 16.5e9
