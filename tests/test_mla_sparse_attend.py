"""``ops/mla.py::mla_sparse_attend``, the session lane's only attend,
against the plain form it replaced in ``extend_step`` (kept HERE as
the reference): ``jnp.take`` of every token row's selected latents,
two einsums over ``(b, t)`` and a softmax."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops import mla
from predictionio_tpu.ops.seqrec import SeqRecParams

T = 8
SPEC = mla.glm_spec(SeqRecParams(
    block="glm_moe_dsa", rank=32, n_heads=4, norm="rmsnorm",
    positions="rope", tied=False, q_lora_rank=16, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, index_n_heads=8,
    index_head_dim=8, index_topk=16, n_experts=8, expert_width=16,
    experts_per_token=2))
H, W = SPEC.n_heads, SPEC.lat_width


def plain(qf, pool, phys, ok, spec):
    g = jnp.take(pool, phys, axis=0)
    s = mla._ein("bthc,btkc->bthk", qf, g, spec) * spec.scale
    a = jax.nn.softmax(jnp.where(ok[:, :, None, :], s, -jnp.inf), axis=-1)
    return mla._ein("bthk,btkc->bthc", a, g, spec)


def problem(B, K, dtype, n_rows=64, seed=0):
    rng = np.random.default_rng(seed)
    qf = jnp.asarray(3 * rng.normal(size=(B, T, H, W)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(n_rows, W)), dtype)
    phys = rng.integers(0, n_rows, (B, T, K)).astype(np.int32)
    # fewer eligible keys than K in most rows; a row always has one
    ok = rng.random((B, T, K)) < 0.7
    ok[..., 0] = True
    ok[0, 0] = True
    return qf, pool, jnp.asarray(phys), jnp.asarray(ok)


def rows_valid(n_new):
    return np.arange(T)[None, :] < np.asarray(n_new)[:, None]


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5),
                                       ("bfloat16", 1e-3)])
@pytest.mark.parametrize("K", [32, 20, 1])
@pytest.mark.parametrize("n_new", [[0], [1], [T], [0, 1, T, 3]],
                         ids=["B1-none", "B1-one", "B1-all", "B4-mixed"])
def test_the_loop_matches_the_plain_form(n_new, K, dtype, tol):
    B = len(n_new)
    spec = dataclasses.replace(SPEC, compute_dtype=dtype)
    qf, pool, phys, ok = problem(B, K, dtype, seed=K + B)
    got = jax.jit(mla.mla_sparse_attend, static_argnums=5)(
        qf, pool, phys, ok, jnp.asarray(n_new, jnp.int32), spec)
    assert got.shape == (B, T, H, W) and got.dtype == jnp.float32
    want = plain(qf, pool, phys, ok, spec)
    valid = rows_valid(n_new)
    np.testing.assert_allclose(np.asarray(got)[valid],
                               np.asarray(want)[valid], atol=tol)
    assert not np.asarray(got)[~valid].any()     # skipped rows read zero


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_padded_token_rows_touch_nothing(dtype):
    """NaN in every pool row that only PADDED token rows point at: the
    result is finite and the padded rows read zero, so they were never
    gathered, scored or summed (a product with a zero weight would
    still have carried the NaN)."""
    n_new = [2, 0, T, 1]
    spec = dataclasses.replace(SPEC, compute_dtype=dtype)
    qf, pool, phys, ok = problem(4, 32, dtype, seed=5)
    valid = rows_valid(n_new)
    phys = jnp.asarray(np.where(valid[..., None], np.asarray(phys) % 32,
                                32 + np.asarray(phys) % 32), jnp.int32)
    poisoned = pool.at[32:].set(jnp.nan)
    n_new = jnp.asarray(n_new, jnp.int32)
    assert not np.isfinite(plain(qf, poisoned, phys, ok, spec)).all()
    got = np.asarray(mla.mla_sparse_attend(qf, poisoned, phys, ok, n_new,
                                           spec))
    assert np.isfinite(got).all()
    assert not got[~valid].any()
    clean = np.asarray(mla.mla_sparse_attend(qf, pool, phys, ok, n_new,
                                             spec))
    np.testing.assert_array_equal(got[valid], clean[valid])
