"""Deviceless compiles for the real chip of the mixed-session cell's
two programs at the PUBLISHED widths: one extend dispatch (8 query rows
x 8 token rows, the paged attention kernel with a first visible
position a row over a window layer's table and without one over a
global layer's, groups of 7 query heads padded to whole sublanes) and
one prefill chunk (2,048 events). Their temporaries are read beside
what the deployment holds resident, so an out-of-memory is found here
and not on the chip; the pools are donated and written in place.
Nothing runs: no result, no time. ``jax.default_backend`` says ``tpu``
for the length of the trace (the grouped matmuls and the paged
attention are the Pallas kernels there)."""

import functools

import numpy as np
import pytest

from benchmark.harness.cell import load_cell
from benchmark.models import swarec


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


@pytest.mark.parametrize("S", [8192, 16384])
def test_extend_and_prefill_compile_and_fit(one_chip, as_tpu, S):
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import smallthinker
    from predictionio_tpu.ops.sessions import (
        SESS_BLOCK,
        SESS_EVENTS,
        SESS_MAX_BATCH,
        SWA_CHUNK,
        LayerKind,
        kind_layout,
    )

    config = load_cell("seqrec-smallthinker.sess-mixed").config
    params = swarec.seqrec_params(config, seed=1)
    spec = smallthinker.swa_spec(params)
    assert spec.pattern == (0, 1, 1, 1, 0, 1, 1, 1) and spec.group == 7
    V, bf16 = int(config["vocab_size"]), jnp.bfloat16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    theta = {name: sds(shape, bf16 if smallthinker.is_low(name)
                       else jnp.float32)
             for name, shape, _ in smallthinker.theta_shapes(V, spec)}
    n_params = sum(int(np.prod(a.shape)) for a in theta.values())
    assert n_params == pytest.approx(3.967e9, rel=0.005)    # 7.93 GB
    Y = theta.pop("out_emb")
    bs = SESS_BLOCK
    kinds = tuple(LayerKind(*k) for k in spec.kinds)
    # the global kind's blocks, and the window kind's share of them
    # (749 of the stored histories' 1,224 blocks lie under the window)
    nb = [1 + int(config["session"]["pool_tokens"]) // bs]
    nb.append(1 + -(-(nb[0] - 1) * 749 // 1224))
    pool = {n: tuple(sds((nb[spec.kind_of(i)], bs, spec.kv_width), bf16)
                     for i in range(spec.n_layers)) for n in ("k", "v")}
    pool_bytes = 2 * 2 * bs * spec.kv_width * (2 * nb[0] + 6 * nb[1])
    assert pool_bytes == pytest.approx(4.28e9, rel=0.02)
    n_users = int(config["shape"]["n_users"]) + 1
    words = -(-(-(-V // 32)) // 128) * 128
    seen = sds((n_users, words), jnp.int32)
    X = sds((n_users, spec.width), bf16)
    B, T = SESS_MAX_BATCH, SESS_EVENTS
    layout, width = kind_layout(kinds, T, S, bs)
    ext = jax.jit(functools.partial(
        smallthinker.extend_step, spec=spec, kb=128, T=T, S=S, bs=bs,
        n_items=V, mode="bf16", layout=layout,
        audit=bool(params.session_audit)),
        donate_argnums=(1, 2, 3)).lower(
        theta, X, seen, pool, Y, sds((B, width), jnp.int32)).compile()
    text = ext.as_text()
    # the paged attention kernel and three grouped matmuls a layer
    assert text.count("tpu_custom_call") >= 4 * spec.n_layers
    assert "paged_gqa_attention" in text
    mem = ext.memory_analysis()
    resident = 2 * n_params + pool_bytes
    assert mem.argument_size_in_bytes == pytest.approx(resident, rel=0.01)
    assert mem.alias_size_in_bytes >= pool_bytes    # the pools, in place
    # no pool is copied to split its rows into heads
    assert f"bf16[{nb[0] * bs},{spec.n_kv},{spec.head_dim}]" not in text
    assert mem.temp_size_in_bytes < 0.4e9
    C = SWA_CHUNK
    layout, width = kind_layout(kinds, C, S, bs)
    pre = jax.jit(functools.partial(
        smallthinker.prefill_chunk, spec=spec, C=C, S=S, bs=bs, qb=32,
        layout=layout), donate_argnums=(1, 2)).lower(
        theta, X, pool, sds((width,), jnp.int32)).compile()
    mem = pre.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 1.2e9
    # resident + the similarity lane's copy of the output table + the
    # kept audits + the largest program's scratch stay inside the
    # chip's 16.9 GB
    assert resident + 0.78e9 + 0.3e9 + mem.temp_size_in_bytes < 16.5e9
