"""``ops/mla.py::mla_select_attend``, the session lane's only attend:
one loop over the VALID token rows that cuts a row's ``index_topk``
best positions, maps them to pool rows, gathers and attends. Against
the batched form it replaced in ``extend_step`` (kept HERE as the
reference): ``lax.top_k`` over ``[B, T, S]``, the block table's
look-up for ``B x T x K`` indices, ``jnp.take`` of every token row's
selected latents, two einsums over ``(b, t)`` and a softmax."""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops import mla
from predictionio_tpu.ops.seqrec import SeqRecParams

T = 8
BS = 4          # cache rows a block (the lane's are 256)
PARAMS = dict(
    block="glm_moe_dsa", rank=32, n_heads=4, norm="rmsnorm",
    positions="rope", tied=False, q_lora_rank=16, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, index_n_heads=8,
    index_head_dim=8, index_topk=16, n_experts=8, expert_width=16,
    experts_per_token=2)
SPEC = mla.glm_spec(SeqRecParams(**PARAMS))
H, W = SPEC.n_heads, SPEC.lat_width


def batched(qf, I, table, pool, spec):
    """The form before: every token row of the bucket cut and
    attended. ``(out [B, T, H, W], idx [B, T, K], ok [B, T, K])``."""
    B = qf.shape[0]
    K = min(spec.idx_topk, I.shape[-1])
    vals, idx = jax.lax.top_k(I, K)
    ok = vals > -jnp.inf
    blk = jnp.take_along_axis(
        table, (idx // BS).reshape(B, T * K), axis=1).reshape(B, T, K)
    g = jnp.take(pool, blk * BS + idx % BS, axis=0)
    s = mla._ein("bthc,btkc->bthk", qf, g, spec) * spec.scale
    a = jax.nn.softmax(jnp.where(ok[:, :, None, :], s, -jnp.inf), axis=-1)
    return mla._ein("bthk,btkc->bthc", a, g, spec), idx, ok


def problem(B, S, dtype, seed=0, levels=0):
    """Absorbed queries, masked index scores, block tables and a pool.
    Query 0's session is 3 events long, so its rows have fewer
    eligible keys than any K below; ``levels``: round the scores to
    that many distinct values (ties at every cut)."""
    rng = np.random.default_rng(seed)
    n_blocks = B * (S // BS) + 8
    qf = jnp.asarray(3 * rng.normal(size=(B, T, H, W)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(n_blocks * BS, W)), dtype)
    table = rng.permutation(n_blocks)[:B * (S // BS)].reshape(B, -1)
    len0 = rng.integers(0, S - T + 1, B)
    len0[0] = 3
    tpos = len0[:, None] + np.arange(T)[None, :]
    I = rng.normal(size=(B, T, S)).astype(np.float32)
    if levels:
        I = np.round(I * levels / 4) * 4 / levels
    I = np.where(np.arange(S)[None, None, :] <= tpos[:, :, None], I, -np.inf)
    return qf, jnp.asarray(I), jnp.asarray(table, jnp.int32), pool


def rows_valid(n_new):
    return np.arange(T)[None, :] < np.asarray(n_new)[:, None]


def run(qf, I, table, pool, n_new, spec, audit=True):
    return jax.jit(functools.partial(
        mla.mla_select_attend, spec=spec, bs=BS, audit=audit))(
            qf, I, table, pool, jnp.asarray(n_new, jnp.int32))


N_NEW = {"B1-none": [0], "B1-one": [1], "B1-three": [3], "B1-all": [T],
         "B4-mixed": [0, 1, T, 3], "B8-mixed": [1, 0, 3, T, 2, 1, 0, 5]}


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5),
                                       ("bfloat16", 1e-3)])
@pytest.mark.parametrize("S,K", [(96, 20), (256, 32), (64, 1)])
@pytest.mark.parametrize("n_new", list(N_NEW.values()), ids=list(N_NEW))
def test_the_loop_matches_the_batched_form(n_new, S, K, dtype, tol):
    """Valid rows: the batched form's outputs, the count of kept
    positions and, for each query's last event, the kept SET. Padded
    rows: zeros, and -1 for a query that brought nothing."""
    B = len(n_new)
    spec = dataclasses.replace(SPEC, compute_dtype=dtype, idx_topk=K)
    qf, I, table, pool = problem(B, S, dtype, seed=K + B)
    got, kept, selected = run(qf, I, table, pool, n_new, spec)
    assert got.shape == (B, T, H, W) and got.dtype == jnp.float32
    want, idx, ok = batched(qf, I, table, pool, spec)
    valid = rows_valid(n_new)
    np.testing.assert_allclose(np.asarray(got)[valid],
                               np.asarray(want)[valid], atol=tol)
    assert not np.asarray(got)[~valid].any()     # skipped rows read zero
    assert int(kept) == int(np.asarray(ok)[valid].sum())
    selected = np.asarray(selected)
    assert selected.shape == (B, K)
    for b, n in enumerate(n_new):
        mine = selected[b][selected[b] >= 0]
        assert len(mine) == len(set(mine.tolist()))
        if n == 0:
            assert not len(mine)
        else:
            assert set(mine.tolist()) == set(
                np.asarray(idx)[b, n - 1][np.asarray(ok)[b, n - 1]].tolist())


@pytest.mark.parametrize("S,K", [(96, 20), (256, 32), (128, 16)])
def test_a_tied_cut_keeps_exactly_k(S, K):
    """Scores of a few distinct values: the ``K``-th is tied in every
    row. Which of the tied positions are kept is free; how many is
    not, nor that every higher score is in. EVERY valid row's cut is
    read (a query cut short at ``t`` events shows row ``t - 1``)."""
    n_new = [T, 5, T, 2]
    spec = dataclasses.replace(SPEC, idx_topk=K)
    qf, I, table, pool = problem(4, S, "float32", seed=S, levels=3)
    scores = np.asarray(I)
    vals = np.asarray(jax.lax.top_k(I, K)[0])
    tied = 0
    for t in range(1, T + 1):
        upto = np.minimum(n_new, t)
        _, kept, selected = run(qf, I, table, pool, upto, spec)
        selected = np.asarray(selected)
        total = 0
        for b in range(4):
            row = scores[b, upto[b] - 1]
            mine = selected[b][selected[b] >= 0]
            assert len(mine) == len(set(mine.tolist())) \
                == min(K, int((row > -np.inf).sum()))
            np.testing.assert_array_equal(
                np.sort(row[mine]), np.sort(vals[b, upto[b] - 1][:len(mine)]))
            tied += len(mine) == K and (row == row[mine].min()).sum() \
                > (row[mine] == row[mine].min()).sum()
            total += sum(min(K, int((scores[b, u] > -np.inf).sum()))
                         for u in range(upto[b]))
        assert int(kept) == total
    assert tied > 8      # the case was there: ties cut through


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_padded_token_rows_touch_nothing(dtype):
    """NaN scores in every PADDED token row, and NaN in every pool row
    that only padded rows' cuts would point at: the result is finite,
    the padded rows read zero and the count is the valid rows' alone,
    so those rows were never cut, gathered, scored or summed (a product
    with a zero weight would still have carried the NaN)."""
    n_new = [2, 0, T, 1]
    S, K = 96, 20
    spec = dataclasses.replace(SPEC, compute_dtype=dtype, idx_topk=K)
    qf, I, table, pool = problem(4, S, dtype, seed=5)
    valid = rows_valid(n_new)
    _, idx, ok = batched(qf, I, table, pool, spec)
    idx, ok, tab = np.asarray(idx), np.asarray(ok), np.asarray(table)
    phys = np.take_along_axis(tab[:, None, :].repeat(T, 1), idx // BS,
                              axis=2) * BS + idx % BS
    mine = np.zeros(pool.shape[0], bool)
    mine[phys[valid][ok[valid]]] = True
    mine[0] = True     # (what a valid row's slots past its keys name)
    poisoned = jnp.where(jnp.asarray(mine)[:, None], pool, jnp.nan)
    bad_I = jnp.where(jnp.asarray(valid)[..., None], I, jnp.nan)
    assert not np.isfinite(batched(qf, bad_I, table, poisoned, spec)[0]).all()
    got, kept, selected = run(qf, bad_I, table, poisoned, n_new, spec)
    assert np.isfinite(np.asarray(got)).all()
    assert not np.asarray(got)[~valid].any()
    assert (np.asarray(selected)[1] == -1).all()
    clean, kept0, selected0 = run(qf, I, table, pool, n_new, spec)
    np.testing.assert_array_equal(np.asarray(got)[valid],
                                  np.asarray(clean)[valid])
    assert int(kept) == int(kept0) == int(ok[valid].sum())
    np.testing.assert_array_equal(selected, selected0)


def test_without_audit_nothing_is_kept():
    qf, I, table, pool = problem(1, 96, "float32")
    out, kept, selected = run(qf, I, table, pool, [3], SPEC, audit=False)
    assert selected is None and int(kept) == 4 + 5 + 6


# -- the whole extend program ----------------------------------------------------

def extend_problem(B=4, S=128, seed=0):
    params = SeqRecParams(**dict(
        PARAMS, n_layers=2, n_dense_layers=1, dense_width=48,
        n_shared_experts=1, seed=seed))
    spec = mla.glm_spec(params)
    V, n_users = 50, 8
    theta = mla.draw_serving_theta(V, params)
    rng = np.random.default_rng(seed)
    n_blocks = B * (S // BS) + 4
    pools = [tuple(jnp.asarray(rng.normal(size=(n_blocks, BS, w)),
                               jnp.float32) for _ in range(spec.n_layers))
             for w in (spec.lat_width, spec.idx_dim)]
    table = rng.permutation(n_blocks)[:B * (S // BS)].reshape(B, -1)
    n_new = np.asarray([3, 0, T, 1][:B])
    len0 = np.asarray([40, 9, S - T, 2][:B])
    pos = len0[:, None] + np.arange(T)[None, :]
    wrow = np.take_along_axis(table, pos // BS, axis=1) * BS + pos % BS
    wrow = np.where(rows_valid(n_new), wrow, n_blocks * BS)   # dropped
    ints = np.concatenate([
        np.arange(B)[:, None], len0[:, None], n_new[:, None],
        rng.integers(0, V, (B, T)), wrow, table], axis=1).astype(np.int32)
    X = jnp.asarray(rng.normal(size=(n_users, spec.width)), jnp.float32)
    seen = jnp.zeros((n_users, 128), jnp.int32)
    Y = theta["out_emb"].astype(jnp.float32)
    kw = dict(spec=spec, kb=8, T=T, S=S, bs=BS, n_items=V, mode="fp32",
              mask_seen=True, audit=True)
    return (theta, X, seen, pools[0], pools[1], Y, jnp.asarray(ints)), kw


def batched_select_attend(qf, I, table, pool, n_new, *, spec, bs, audit):
    """``mla_select_attend``'s contract by the batched form."""
    out, idx, ok = batched(qf, I, table, pool, spec)
    valid = jnp.arange(T)[None, :] < n_new[:, None]
    last = jnp.maximum(n_new - 1, 0)[:, None, None]
    selected = jnp.where(
        (n_new > 0)[:, None],
        jnp.take_along_axis(jnp.where(ok, idx, -1), last, axis=1)[:, 0], -1)
    return (jnp.where(valid[..., None, None], out, 0.0),
            jnp.sum(jnp.where(valid, jnp.sum(ok, -1), 0)), selected)


_CUTS = re.compile(r"(?<![a-z_])(sort|top_?k)(?![a-z_])", re.I)


def cuts_over(text: str, shape) -> list:
    """The lines of a program's text that sort or cut an operand of
    ``shape`` (as StableHLO ``4x8x128x`` or HLO ``[4,8,128]``
    writes it)."""
    dims = [str(d) for d in shape]
    marks = ("x".join(dims) + "x", "[" + ",".join(dims) + "]")
    return [ln for ln in text.splitlines()
            if _CUTS.search(ln) and any(m in ln for m in marks)]


def test_the_extend_program_cuts_no_padded_row(monkeypatch):
    """``extend_step`` at ``B = 4``: neither as lowered nor as compiled
    (for the CPU) does the program sort or cut an operand of the
    padded shape ``[4, 8, S]``, where the batched form in its place
    does; and one dispatch gives the batched form's counters
    (``selected``, ``eligible``), answers and audits."""
    args, kw = extend_problem()
    B, S = 4, kw["S"]

    def program():
        lowered = jax.jit(functools.partial(mla.extend_step, **kw)).lower(
            *args)
        compiled = lowered.compile()
        return lowered.as_text() + compiled.as_text(), compiled(*args)

    text, got = program()
    assert "while" in text and not cuts_over(text, (B, T, S))
    monkeypatch.setattr(mla, "mla_select_attend", batched_select_attend)
    text, want = program()
    assert cuts_over(text, (B, T, S))      # the search finds what it must

    def counters(packed):
        return np.asarray(packed)[0, 2 * kw["kb"]:].view(np.float32)

    np.testing.assert_array_equal(counters(got[0])[:2],
                                  counters(want[0])[:2])
    n_new, len0 = np.asarray(args[-1])[:, 2], np.asarray(args[-1])[:, 1]
    K, layers = kw["spec"].idx_topk, kw["spec"].n_layers
    tpos1 = (len0[:, None] + np.arange(T) + 1)[rows_valid(n_new)]
    assert counters(got[0])[0] == layers * np.minimum(tpos1, K).sum()
    assert counters(got[0])[1] == layers * np.minimum(tpos1, S).sum()
    kb = kw["kb"]
    np.testing.assert_array_equal(np.asarray(got[0])[:, kb:2 * kb],
                                  np.asarray(want[0])[:, kb:2 * kb])
    np.testing.assert_allclose(
        np.asarray(got[0])[:, :kb].view(np.float32),
        np.asarray(want[0])[:, :kb].view(np.float32), atol=1e-5)
    for k in ("scores", "layers", "lat", "ik", "gates", "h2"):
        np.testing.assert_allclose(got[5][k], want[5][k], atol=1e-5)
    np.testing.assert_array_equal(got[5]["picks"], want[5]["picks"])
    np.testing.assert_array_equal(np.sort(got[5]["selected"], axis=-1),
                                  np.sort(want[5]["selected"], axis=-1))
    assert (np.asarray(got[5]["selected"])[:, 1] == -1).all()
