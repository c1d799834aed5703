"""Deviceless compiles for the real chip of the hybrid-session cell's
two programs at the PUBLISHED widths: one extend dispatch (8 query rows
x 8 token rows: in each of six layers the recurrent SSD form over the
sessions' 4 MB slots AND the paged attention kernel at heads of 128, 5
query heads a key/value head, over the same layer's table, then the
dense SwiGLU of 21,504) and one prefill chunk (2,048 events: the
chunked SSD form, 16 chunks of 128). Their temporaries are read beside
what the deployment holds resident, so an out-of-memory is found here
and not on the chip; slots and blocks are donated and written in place.
Nothing runs: no result, no time. ``jax.default_backend`` says ``tpu``
for the length of the trace (the paged attention is the Pallas kernel
there). Both mixer forms are XLA: there is no kernel of this block's
own to compile."""

import functools

import numpy as np
import pytest

from benchmark.harness.cell import load_cell
from benchmark.models import hybrec

CELL = "seqrec-falconh1.sess-hybrid"


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


def programs(one_chip, S: int, B: int):
    """The cell's shapes on a described chip: ``(spec, theta, Y, pool,
    (blocks, slots), a function of the program's name that lowers and
    compiles it)``."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import falconh1
    from predictionio_tpu.ops.sessions import (
        HYB_CHUNK,
        SESS_BLOCK,
        SESS_EVENTS,
        LayerKind,
        kind_layout,
    )

    config = load_cell(CELL).config
    params = hybrec.seqrec_params(config, seed=1)
    spec = falconh1.hyb_spec(params)
    V, bf16 = int(config["vocab_size"]), jnp.bfloat16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    theta = {name: sds(shape, bf16 if falconh1.is_low(name)
                       else jnp.float32)
             for name, shape, _ in falconh1.theta_shapes(V, spec)}
    Y = theta.pop("out_emb")
    bs = SESS_BLOCK
    kinds = tuple(LayerKind(*k) for k in spec.kinds)
    nb = 1 + int(config["session"]["pool_tokens"]) // bs
    # the stored histories' 49 sessions hold 1,252 of the 1,440 blocks
    slots = 1 + -(-(nb - 1) * 49 // 1252)
    L = spec.n_layers
    pool = {n: tuple(sds((nb, bs, spec.kv_width), bf16) for _ in range(L))
            for n in ("k", "v")}
    for name, shape, dtype, _ in spec.state_shapes:
        pool[name] = tuple(sds((slots,) + shape, jnp.dtype(dtype))
                           for _ in range(L))
    n_users = int(config["shape"]["n_users"]) + 1
    words = -(-(-(-V // 32)) // 128) * 128
    seen = sds((n_users, words), jnp.int32)
    X = sds((n_users, spec.width), bf16)

    def compiled(which: str):
        if which == "extend":
            layout, width = kind_layout(kinds, SESS_EVENTS, S, bs)
            return jax.jit(functools.partial(
                falconh1.extend_step, spec=spec, kb=128, T=SESS_EVENTS,
                S=S, bs=bs, n_items=V, mode="bf16", layout=layout,
                audit=bool(params.session_audit)),
                donate_argnums=(1, 2, 3)).lower(
                theta, X, seen, pool, Y,
                sds((B, width), jnp.int32)).compile()
        layout, width = kind_layout(kinds, HYB_CHUNK, S, bs)
        return jax.jit(functools.partial(
            falconh1.prefill_chunk, spec=spec, C=HYB_CHUNK, S=S, bs=bs,
            qb=32, layout=layout), donate_argnums=(1, 2)).lower(
            theta, X, pool, sds((width,), jnp.int32)).compile()

    return spec, theta, Y, pool, (nb, slots), compiled


def nbytes(tree) -> int:
    import jax

    return int(sum(np.prod(a.shape) * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves(tree)))


@pytest.mark.parametrize("S, B", [(8192, 8), (32768, 8), (16384, 1)])
def test_extend_compiles_and_fits(one_chip, as_tpu, S, B):
    spec, theta, Y, pool, (nb, slots), compiled = programs(one_chip, S, B)
    assert (spec.group, spec.head_dim, spec.ssm_heads, spec.d_state,
            spec.mlp_width, spec.n_layers) == (5, 128, 32, 256, 21504, 6)
    n_params = nbytes(theta) + nbytes(Y)
    assert n_params == pytest.approx(6.50e9, rel=0.01)
    assert (nb, slots) == (1441, 58)
    pool_bytes = nbytes(pool)
    assert nbytes({n: pool[n] for n in ("k", "v")}) \
        == pytest.approx(4.53e9, rel=0.01)
    assert nbytes({n: pool[n] for n in ("state", "tail")}) \
        == pytest.approx(1.47e9, rel=0.01)
    ext = compiled("extend")
    text = ext.as_text()
    # the paged attention kernel in every layer
    assert text.count("tpu_custom_call") >= spec.n_layers
    assert "paged_gqa_attention" in text
    mem = ext.memory_analysis()
    assert mem.argument_size_in_bytes == pytest.approx(
        n_params + pool_bytes, rel=0.01)
    assert mem.alias_size_in_bytes >= pool_bytes    # slots and blocks, in place
    # no pool is copied to split its rows into heads
    assert f"bf16[{nb * 256},{spec.n_kv},{spec.head_dim}]" not in text
    assert mem.temp_size_in_bytes < 1.2e9


def test_prefill_compiles_and_fits(one_chip, as_tpu):
    spec, theta, Y, pool, _, compiled = programs(one_chip, 32768, 8)
    pre = compiled("prefill")
    mem = pre.memory_analysis()
    pool_bytes = nbytes(pool)
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 2.0e9
    # resident + the similarity lane's copy of the output slice + the
    # kept audits + the largest program's scratch stay inside the
    # chip's 16.9 GB
    assert nbytes(theta) + nbytes(Y) + pool_bytes + 0.67e9 + 0.3e9 \
        + mem.temp_size_in_bytes < 16.5e9
