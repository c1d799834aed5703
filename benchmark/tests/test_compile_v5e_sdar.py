"""Deviceless compiles for the real chip of the slate cell's three
programs at the PUBLISHED widths: one round of the slate lane (8 query
rows x a block of 4, the denoising loop, the commit pass, the paged
attention kernel over sessions of up to 65,536 cached events), one
commit of new events (8 x 8 tokens) and one prefill chunk (2,048
events). Their temporaries are read beside what the deployment holds
resident, so an out-of-memory is found here and not on the chip; the
pools are donated and written in place. Nothing runs: no result, no
time. ``jax.default_backend`` says ``tpu`` for the length of the trace
(the grouped matmuls and the paged attention are the Pallas kernels
there)."""

import functools

import numpy as np
import pytest

from benchmark.harness.cell import load_cell
from benchmark.models import slaterec


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


@pytest.mark.parametrize("S", [8192, 65536])
def test_round_events_and_prefill_compile_and_fit(one_chip, as_tpu, S):
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import sdar
    from predictionio_tpu.ops.sessions import SESS_BLOCK
    from predictionio_tpu.ops.slates import (
        EVENT_ROWS,
        SLATE_CHUNK,
        SLATE_MAX,
        SLATE_MAX_BATCH,
    )

    config = load_cell("seqrec-sdar.slate-gen").config
    params = slaterec.seqrec_params(config, seed=1)
    spec = sdar.sdar_spec(params)
    V, bf16 = int(config["vocab_size"]), jnp.bfloat16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    theta = {name: sds(shape, bf16 if sdar.is_low(name) else jnp.float32)
             for name, shape, _ in sdar.theta_shapes(V, spec)}
    n_params = sum(int(np.prod(a.shape)) for a in theta.values())
    assert n_params == pytest.approx(4.36e9, rel=0.005)
    Y = theta.pop("out_emb")
    bs = SESS_BLOCK
    nb = 1 + int(config["session"]["pool_tokens"]) // bs
    pool = {n: tuple(sds((nb, bs, spec.kv_width), bf16)
                     for _ in range(spec.n_layers)) for n in ("k", "v")}
    pool_bytes = 2 * 2 * nb * bs * spec.kv_width * spec.n_layers
    assert pool_bytes == pytest.approx(4.43e9, rel=0.01)
    n_users = int(config["shape"]["n_users"])
    words = -(-(-(-V // 32)) // 128) * 128
    seen = sds((n_users, words), jnp.int32)
    B, R = SLATE_MAX_BATCH, spec.block_len
    rnd = jax.jit(functools.partial(
        sdar.slate_round, spec=spec, S=S, J=SLATE_MAX, bs=bs, n_items=V,
        mode="bf16", audit=bool(params.session_audit)),
        donate_argnums=(1, 2)).lower(
        theta, seen, pool, Y,
        sds((B, sdar.round_width(R, SLATE_MAX, S, bs)), jnp.int32)).compile()
    text = rnd.as_text()
    # the paged attention kernel and three grouped matmuls a layer, in
    # the loop's body and in the commit pass (whose last layer's experts
    # nothing reads: only its keys and values are kept)
    assert text.count("tpu_custom_call") >= 2 * 4 * spec.n_layers - 3
    assert "paged_gqa_attention" in text
    mem = rnd.memory_analysis()
    resident = 2 * n_params + pool_bytes
    assert mem.argument_size_in_bytes == pytest.approx(resident, rel=0.01)
    assert mem.alias_size_in_bytes >= pool_bytes    # the pools, in place
    # no pool is copied to split its rows into heads (a reshape of its
    # minor dimension is a physical copy of 369 MB: PR 33's first trace
    # found twelve a round); the scratch rows are gathered whole
    assert f"bf16[{nb * bs},{spec.n_kv},{spec.head_dim}]" not in text
    assert mem.temp_size_in_bytes < 0.4e9
    ev = jax.jit(functools.partial(
        sdar.commit_events, spec=spec, T=EVENT_ROWS, S=S, bs=bs,
        audit=bool(params.session_audit)), donate_argnums=(1, 2)).lower(
        theta, seen, pool,
        sds((B, sdar.events_width(EVENT_ROWS, S, bs)), jnp.int32)).compile()
    assert ev.memory_analysis().alias_size_in_bytes >= pool_bytes
    assert ev.memory_analysis().temp_size_in_bytes < 0.5e9
    C = SLATE_CHUNK
    pre = jax.jit(functools.partial(
        sdar.prefill_chunk, spec=spec, C=C, S=S, bs=bs, qb=32),
        donate_argnums=(1, 2)).lower(
        theta, sds((n_users, spec.width), bf16), pool,
        sds((3 + 2 * C + S // bs,), jnp.int32)).compile()
    mem = pre.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 1.0e9
    # resident + the similarity lane's copy of the output table + the
    # kept audits + the largest program's scratch stay inside the
    # chip's 16.9 GB
    assert resident + 0.62e9 + 0.35e9 + mem.temp_size_in_bytes < 16.5e9
