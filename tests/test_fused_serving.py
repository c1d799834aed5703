"""The fused gather->score->mask->top-k serving kernel vs the XLA
chain (interpret mode on CPU — semantics identical to TPU execution).

Exact-agreement strategy: the fp32 suites draw INTEGER-valued factors,
so every score is an exact small-integer dot product — bitwise
identical whatever reduction order the two implementations use — and
``assert_array_equal`` on indices AND scores is meaningful. The
continuous-data suites assert allclose + index-set agreement instead
(fp32 reduction order may differ in the last ulp). Slots whose score
is -inf carry no defined index in either implementation and are
excluded, exactly as every caller filters them."""

import numpy as np
import pytest

import jax.numpy as jnp

from predictionio_tpu.ops.als_pallas import (
    TOPK_TILE_M,
    fused_gather_score_topk,
    pack_seen_ids,
)
from predictionio_tpu.ops.quantize import (
    QuantFactors,
    dequantize_rows_np,
    quantize_rows_int8,
)
from predictionio_tpu.ops.serving import DeviceTopK

pytestmark = pytest.mark.pallas


def xla_chain_topk(Q, Y, seen_cols, seen_mask, k, n_items):
    """The reference gather/einsum/mask/top-k chain, per query row."""
    scores = np.asarray(Y, dtype=np.float32) @ \
        np.asarray(Q, dtype=np.float32).T            # [M, B]
    if seen_cols is not None:
        L, B = seen_cols.shape
        for l in range(L):
            for b in range(B):
                if seen_mask[l, b] > 0:
                    scores[seen_cols[l, b], b] = -np.inf
    scores[n_items:, :] = -np.inf
    idx = np.empty((Q.shape[0], k), dtype=np.int64)
    vals = np.empty((Q.shape[0], k), dtype=np.float32)
    for b in range(Q.shape[0]):
        order = np.argsort(-scores[:, b], kind="stable")[:k]
        idx[b] = order
        vals[b] = scores[order, b]
    return vals, idx


def seen_bits(seen_cols, seen_mask, n_pos):
    """The tests' ``[L, B]`` id lists as the kernel's packed bitmap."""
    return pack_seen_ids(seen_cols.T, seen_mask.T > 0, n_pos)


def int_factors(rng, shape, lo=-6, hi=7):
    return rng.integers(lo, hi, shape).astype(np.float32)


class TestKernelExactAgreement:
    @pytest.mark.parametrize("B,M,R,L,k", [
        (1, 17, 4, 1, 5),        # single query, sub-tile catalog
        (5, 33, 6, 4, 7),        # odd everything
        (8, 128, 8, 8, 16),      # exactly one tile
        (3, 300, 8, 6, 16),      # multi-tile with partial pad
    ])
    def test_masked_fp32_exact(self, B, M, R, L, k):
        rng = np.random.default_rng(B * M + k)
        Q = int_factors(rng, (B, R))
        Y = int_factors(rng, (M, R))
        sc = rng.integers(0, M, (L, B)).astype(np.int32)
        sm = (rng.random((L, B)) < 0.7).astype(np.float32)
        n_items = M - 2
        vals, idx, _ = fused_gather_score_topk(
            jnp.asarray(Q), jnp.asarray(Y), seen_bits(sc, sm, M), k=k,
            n_items=n_items, mask_seen=True, interpret=True)
        wv, wi = xla_chain_topk(Q, Y, sc, sm, k, n_items)
        vals, idx = np.asarray(vals), np.asarray(idx)
        fin = np.isfinite(wv)
        np.testing.assert_array_equal(idx[fin], wi[fin])
        np.testing.assert_array_equal(vals[fin], wv[fin])
        # -inf slots agree on being -inf
        assert (vals[~fin] == -np.inf).all()

    def test_no_mask_exact(self):
        rng = np.random.default_rng(0)
        Q = int_factors(rng, (4, 5))
        Y = int_factors(rng, (40, 5))
        vals, idx, _ = fused_gather_score_topk(
            jnp.asarray(Q), jnp.asarray(Y), k=6,
            n_items=40, mask_seen=False, interpret=True)
        wv, wi = xla_chain_topk(Q, Y, None, None, 6, 40)
        np.testing.assert_array_equal(np.asarray(idx), wi)
        np.testing.assert_array_equal(np.asarray(vals), wv)

    def test_tie_break_lowest_index_first(self):
        """Duplicate item rows produce tied scores; lax.top_k (and the
        chain) keep the LOWEST item id first — the kernel's running
        heap must reproduce that across tile boundaries."""
        Q = np.asarray([[1.0, 0.0]], dtype=np.float32)
        Y = np.zeros((200, 2), dtype=np.float32)
        Y[:, 0] = 7.0                      # every item ties at score 7
        vals, idx, _ = fused_gather_score_topk(
            jnp.asarray(Q), jnp.asarray(Y), k=5,
            n_items=200, mask_seen=False, interpret=True)
        np.testing.assert_array_equal(np.asarray(idx)[0],
                                      [0, 1, 2, 3, 4])
        assert (np.asarray(vals)[0] == 7.0).all()

    def test_all_masked_returns_neg_inf(self):
        Q = np.ones((2, 3), dtype=np.float32)
        Y = np.ones((10, 3), dtype=np.float32)
        sc = np.tile(np.arange(10, dtype=np.int32)[:, None], (1, 2))
        sm = np.ones((10, 2), dtype=np.float32)
        vals, _, _ = fused_gather_score_topk(
            jnp.asarray(Q), jnp.asarray(Y), seen_bits(sc, sm, 10), k=4,
            n_items=10, mask_seen=True, interpret=True)
        assert (np.asarray(vals) == -np.inf).all()

    def test_continuous_data_allclose(self):
        rng = np.random.default_rng(7)
        Q = rng.normal(size=(6, 8)).astype(np.float32)
        Y = rng.normal(size=(150, 8)).astype(np.float32)
        vals, idx, _ = fused_gather_score_topk(
            jnp.asarray(Q), jnp.asarray(Y), k=10,
            n_items=150, mask_seen=False, interpret=True)
        wv, wi = xla_chain_topk(Q, Y, None, None, 10, 150)
        np.testing.assert_allclose(np.asarray(vals), wv, rtol=1e-5)
        for b in range(6):
            assert set(np.asarray(idx)[b].tolist()) == \
                set(wi[b].tolist())


# ---------------------------------------------------------------------------
# the bounded merge (ISSUE 29): a tile costs as many selection rounds as
# the query that gains most from it has newcomers, and the answers stay
# lax.top_k's to the bit
# ---------------------------------------------------------------------------

MERGE_M = 333            # three tiles of 128, the last one part padding
ORDERS = ("random", "rising", "falling", "blocks")
STORES = ("fp32", "bf16", "int8", "row_valid")


def _merge_problem(order, store, B, seed, m=MERGE_M):
    """Integer-valued item rows ``[m, 2]`` and query rows whose scores
    are exact small integers in every store (bf16 holds integers to
    256; the int8 store is built with power-of-two scales), laid out so
    the scores rise with the item id (every tile is all newcomers),
    fall with it (only the first tiles merge), or come in blocks of 37
    equal scores (duplicated item rows: a block straddles the tile edge
    at 128 and the k-th place of K = 16, 32, 64, 128 and 200)."""
    rng = np.random.default_rng(seed)
    i = np.arange(m)
    if order == "random":
        Y = rng.integers(-6, 7, (m, 2))
        Q = rng.integers(-5, 6, (B, 2))
    else:
        Y = np.stack([i // 16, i % 16], axis=1)       # 16 a + b = id
        c = rng.integers(1, 4, (B, 1))
        Q = c * np.asarray([[16, 1]])
        if order == "falling":
            Q = -Q
        elif order == "blocks":
            Y = np.stack([i // 37, np.zeros_like(i)], axis=1)
            Q = -c * np.asarray([[1, 0]])
    Y = Y.astype(np.float32)
    Q = Q.astype(np.float32)
    valid = None
    if store == "row_valid":                           # holes
        valid = (rng.random(m) < 0.75).astype(np.float32)
    if store == "bf16":
        Yd = jnp.asarray(Y).astype(jnp.bfloat16)
    elif store == "int8":
        # data * scale == Y exactly: scales 1 and 2, even rows halved
        half = (Y % 2 == 0).all(axis=1)
        Yd = QuantFactors(
            jnp.asarray(np.where(half[:, None], Y / 2, Y).astype(np.int8)),
            jnp.asarray(np.where(half, 2.0, 1.0).astype(np.float32)))
    else:
        Yd = jnp.asarray(Y)
    return Q, Y, Yd, valid


# the merge tests stream three tiles of a small catalog at the kernel's
# own tile size
MERGE_TM = TOPK_TILE_M
# a wider tile (what PR 28 and PR 29's first round ran): ``tile_m``
# takes any multiple of the seen word
WIDE_TM = 512
# ... and a catalog that is no multiple of either, the last tile ragged
RAGGED_M = 1100


def _rule_rounds(scores, K, tm=MERGE_TM):
    """What the merge rule says a pass costs, counted in numpy:
    ``scores [M, B]`` masked, B a multiple of 8 as the kernel pads it;
    per tile the largest number, over the queries, of tile scores that
    beat the query's k-th as the tile arrives (a score equal to it
    does not), at most ``min(K, tm)``."""
    M, B = scores.shape
    run = np.full((K, B), -np.inf, dtype=np.float32)
    rounds = 0
    for t0 in range(0, M, tm):
        tile = scores[t0:t0 + tm]
        rounds += min(int((tile > run[K - 1]).sum(axis=0).max()), K, tm)
        union = np.concatenate([run, tile])
        run = -np.sort(-union, axis=0, kind="stable")[:K]
    return rounds


def _assert_merge_exact(K, B, order, store, *, m=MERGE_M, tm=MERGE_TM,
                        masked=False, n_items=None, valid=None):
    """One pass of the kernel against ``lax.top_k`` over the same
    masked scores: values to the bit, ids wherever a value is finite
    (the -inf tail past the valid candidates carries no id), and the
    returned round count against :func:`_rule_rounds`."""
    import jax

    from predictionio_tpu.ops.als_pallas import pack_seen_bits

    n_items = m if n_items is None else n_items
    Q, Y, Yd, holes = _merge_problem(order, store, B, seed=K * 31 + B, m=m)
    valid = holes if valid is None else valid
    hit = None
    if masked:
        hit = np.random.default_rng(K + B).random((B, m)) < 0.4
    vals, idx, rounds = fused_gather_score_topk(
        jnp.asarray(Q), Yd,
        pack_seen_bits(jnp.asarray(hit)) if masked else None, k=K,
        n_items=n_items, mask_seen=masked, row_valid=valid,
        interpret=True, tile_m=tm)
    scores = Y @ Q.T                               # [M, B], exact
    scores[n_items:] = -np.inf
    if valid is not None:
        scores[valid <= 0] = -np.inf
    if masked:
        scores[hit.T] = -np.inf
    wv, wi = jax.lax.top_k(jnp.asarray(scores.T), K)
    wv, wi = np.asarray(wv), np.asarray(wi)
    vals, idx = np.asarray(vals), np.asarray(idx)
    np.testing.assert_array_equal(vals, wv)
    fin = np.isfinite(wv)
    np.testing.assert_array_equal(idx[fin], wi[fin])
    # the counter: the kernel pads the query block to a multiple of
    # 8 with zero rows, which score 0 on every valid unmasked item
    # (their seen words are zero padding too)
    padded = np.zeros((m, -(-B // 8) * 8), dtype=np.float32)
    padded[n_items:] = -np.inf
    if valid is not None:
        padded[valid <= 0] = -np.inf
    padded[:, :B] = scores
    assert int(rounds) == _rule_rounds(padded, K, tm)
    return wv


class TestBoundedMerge:
    @pytest.mark.parametrize("store", STORES)
    @pytest.mark.parametrize("order", ORDERS)
    @pytest.mark.parametrize("B", [1, 8, 16])
    @pytest.mark.parametrize("K", [1, 16, 128, 200])
    def test_exact_against_lax_top_k(self, K, B, order, store):
        _assert_merge_exact(K, B, order, store)

    @pytest.mark.parametrize("store", STORES)
    @pytest.mark.parametrize("order", ORDERS)
    @pytest.mark.parametrize("K,B", [(32, 8), (64, 8), (16, 256),
                                     (64, 256), (128, 256)])
    def test_exact_at_the_ladders_other_buckets(self, K, B, order, store):
        """The user lane's K = 32 and 64, and the bucket a backlog or
        ``BatchPredictor`` fills (256 queries: two lane tiles wide)."""
        _assert_merge_exact(K, B, order, store)

    @pytest.mark.parametrize("masked", [False, True])
    @pytest.mark.parametrize("order", ORDERS)
    @pytest.mark.parametrize("K,store", [(16, "bf16"), (64, "int8"),
                                         (128, "fp32"),
                                         (128, "row_valid")])
    def test_exact_at_a_wide_tile(self, K, store, order, masked):
        """512 rows a tile (16 seen words unpacked a step, K under the
        tile) over a catalog that is no multiple of it, with and
        without the seen bitmap."""
        _assert_merge_exact(K, 8, order, store, m=RAGGED_M, tm=WIDE_TM,
                            masked=masked)

    @pytest.mark.parametrize("masked", [False, True])
    @pytest.mark.parametrize("K", [16, 32, 64, 128])
    @pytest.mark.parametrize("how", ["n_items", "row_valid"])
    def test_fewer_valid_candidates_than_k(self, how, K, masked):
        """Eleven real items under K places: the finite prefix is
        ``lax.top_k``'s and the tail is -inf, whichever way the catalog
        says what is real; the padding rows cost no round."""
        valid = None
        if how == "row_valid":
            valid = np.zeros(MERGE_M, np.float32)
            valid[np.arange(11) * 30] = 1.0            # spread over tiles
        wv = _assert_merge_exact(K, 8, "random", "fp32", masked=masked,
                                 n_items=11 if valid is None else None,
                                 valid=valid)
        assert (np.isfinite(wv).sum(axis=1) <= 11).all()
        assert (wv[:, 11:] == -np.inf).all()

    @pytest.mark.parametrize("tm", [MERGE_TM, WIDE_TM])
    @pytest.mark.parametrize("K", [1, 16, 128, 200])
    @pytest.mark.parametrize("order", ["rising", "falling"])
    def test_rounds_closed_form(self, order, K, tm):
        """Falling scores: the list fills from the first tile, K rounds
        in all, and no later tile merges (a K over the tile fills over
        two tiles, and the second is counted against an open k-th: all
        of its rows). Rising scores: every tile is all newcomers,
        min(K, rows) rounds each, which is the old kernel's cost and the
        most the loop can run."""
        Q, _, Yd, _ = _merge_problem(order, "fp32", 8, seed=K)
        _, _, rounds = fused_gather_score_topk(
            jnp.asarray(Q), Yd, k=K, n_items=MERGE_M, mask_seen=False,
            interpret=True, tile_m=tm)
        tiles = [min(tm, MERGE_M - t) for t in range(0, MERGE_M, tm)]
        if order == "rising":
            want = sum(min(K, n) for n in tiles)
        else:
            want = K if K <= tm else 2 * tm
        assert int(rounds) == want

    def test_seen_mask_counts_only_unseen_newcomers(self):
        """With the seen bitmap the masked rows are -inf before the
        merge sees them: they cost no round and never enter."""
        rng = np.random.default_rng(5)
        Q, Y, Yd, _ = _merge_problem("rising", "fp32", 8, seed=5)
        hit = rng.random((8, MERGE_M)) < 0.5
        from predictionio_tpu.ops.als_pallas import pack_seen_bits

        vals, idx, rounds = fused_gather_score_topk(
            jnp.asarray(Q), Yd, pack_seen_bits(jnp.asarray(hit)), k=16,
            n_items=MERGE_M, mask_seen=True, interpret=True,
            tile_m=MERGE_TM)
        scores = Y @ Q.T
        scores[hit.T] = -np.inf
        order = np.argsort(-scores, axis=0, kind="stable")[:16].T
        np.testing.assert_array_equal(np.asarray(idx), order)
        assert int(rounds) == _rule_rounds(scores, 16)


class TestKernelInt8:
    def test_int8_exact_vs_dequant_chain(self):
        """Int8 tiles dequantize in VMEM; with rows whose absmax is
        exactly 127 the scale is 1.0, dequant is exact, and the kernel
        must match the dequantize-then-chain oracle bitwise."""
        rng = np.random.default_rng(11)
        Y = rng.integers(-127, 128, (70, 6)).astype(np.float32)
        Y[:, 0] = 127.0                     # pin scale == 1.0 per row
        Q = rng.integers(-5, 6, (4, 6)).astype(np.float32)
        Yq = quantize_rows_int8(Y)
        vals, idx, _ = fused_gather_score_topk(
            jnp.asarray(Q), Yq, k=8, n_items=70,
            mask_seen=False, interpret=True)
        wv, wi = xla_chain_topk(Q, dequantize_rows_np(Yq), None, None,
                                8, 70)
        np.testing.assert_array_equal(np.asarray(idx), wi)
        np.testing.assert_array_equal(np.asarray(vals), wv)

    def test_int8_random_scales_allclose(self):
        rng = np.random.default_rng(12)
        Y = (rng.normal(size=(90, 5)) * 3).astype(np.float32)
        Q = rng.normal(size=(3, 5)).astype(np.float32)
        Yq = quantize_rows_int8(Y)
        vals, _, _ = fused_gather_score_topk(
            jnp.asarray(Q), Yq, k=6, n_items=90,
            mask_seen=False, interpret=True)
        wv, _ = xla_chain_topk(Q, dequantize_rows_np(Yq), None, None,
                               6, 90)
        np.testing.assert_allclose(np.asarray(vals), wv, rtol=1e-5)


class TestDeviceTopKFusedEndToEnd:
    """PIO_SERVE_KERNEL=fused routes every DeviceTopK dispatch path
    through the kernel; each must agree with its own XLA-chain twin
    (integer factors -> exact)."""

    @pytest.fixture()
    def factor_pair(self):
        rng = np.random.default_rng(21)
        X = int_factors(rng, (20, 6))
        Y = int_factors(rng, (33, 6))
        seen = {u: rng.choice(33, size=rng.integers(1, 6),
                              replace=False)
                for u in range(0, 20, 2)}
        return X, Y, seen

    def _pair(self, monkeypatch, factor_pair, **kw):
        X, Y, seen = factor_pair
        monkeypatch.setenv("PIO_SERVE_KERNEL", "fused")
        fused = DeviceTopK(X, Y, seen, microbatch=False, **kw)
        assert fused._kernel == "fused"
        monkeypatch.setenv("PIO_SERVE_KERNEL", "xla")
        xla = DeviceTopK(X, Y, seen, microbatch=False, **kw)
        assert xla._kernel == "xla"
        return fused, xla

    def test_user_topk_paths_agree(self, monkeypatch, factor_pair):
        fused, xla = self._pair(monkeypatch, factor_pair)
        for uid in (0, 1, 7, 19):
            fi, fs = fused.user_topk(uid, 5)
            xi, xs = xla.user_topk(uid, 5)
            np.testing.assert_array_equal(fi, xi)
            np.testing.assert_array_equal(fs, xs)

    def test_users_topk_bucket_agrees(self, monkeypatch, factor_pair):
        fused, xla = self._pair(monkeypatch, factor_pair)
        uids = np.asarray([0, 3, 7, 12, 19])
        fi, fs = fused.users_topk(uids, 5)
        xi, xs = xla.users_topk(uids, 5)
        fin = np.isfinite(xs)
        np.testing.assert_array_equal(fi[fin], xi[fin])
        np.testing.assert_array_equal(fs[fin], xs[fin])

    def test_items_topk_agrees(self, monkeypatch, factor_pair):
        """Axis-aligned item rows keep the normalized matrix exact, so
        the similarity lane agrees exactly too."""
        rng = np.random.default_rng(5)
        X = int_factors(rng, (6, 4))
        Y = np.zeros((12, 4), dtype=np.float32)
        for m in range(12):  # +-unit one-hots: unit rows, exact norms
            Y[m, m % 4] = 1.0 if m % 3 else -1.0
        monkeypatch.setenv("PIO_SERVE_KERNEL", "fused")
        fused = DeviceTopK(X, Y, microbatch=False)
        monkeypatch.setenv("PIO_SERVE_KERNEL", "xla")
        xla = DeviceTopK(X, Y, microbatch=False)
        fi, fs = fused.items_topk([2, 5], 6)
        xi, xs = xla.items_topk([2, 5], 6)
        np.testing.assert_array_equal(fi, xi)
        np.testing.assert_array_equal(fs, xs)

    def test_int8_store_fused_agrees_with_int8_xla(self, monkeypatch,
                                                   factor_pair):
        monkeypatch.setenv("PIO_SERVE_PRECISION", "int8")
        fused, xla = self._pair(monkeypatch, factor_pair)
        for uid in (0, 4, 9):
            fi, fs = fused.user_topk(uid, 6)
            xi, xs = xla.user_topk(uid, 6)
            np.testing.assert_array_equal(fi, xi)
            np.testing.assert_allclose(fs, xs, rtol=1e-5)

    def test_fused_aot_ladder_and_zero_recompile(self, monkeypatch,
                                                 factor_pair):
        """The fused programs ride the AOT ladder: warmup precompiles
        every entry and steady-state queries hit those executables (the
        serve-time-compile contract the benchmark's
        ``compiles_in_window`` asserts end to end)."""
        from predictionio_tpu.utils import metrics

        X, Y, seen = factor_pair
        monkeypatch.setenv("PIO_SERVE_KERNEL", "fused")
        srv = DeviceTopK(X, Y, seen, microbatch=False)
        stats = srv.warmup(max_k=32)
        assert stats["compiled"] > 0
        metrics.install_jit_compile_listener()
        before = metrics.JIT_COMPILES.value()
        srv.user_topk(3, 5)
        srv.users_topk(np.asarray([1, 2, 3]), 10)
        srv.items_topk([4], 8)
        assert metrics.JIT_COMPILES.value() == before

    def test_patch_users_then_fused_serves_fresh(self, monkeypatch,
                                                 factor_pair):
        fused, xla = self._pair(monkeypatch, factor_pair)
        rng = np.random.default_rng(31)
        fresh = int_factors(rng, (2, 6))
        for srv in (fused, xla):
            srv.patch_users(np.asarray([1, 22]), fresh,
                            seen_items={1: np.asarray([0, 2]),
                                        22: np.asarray([5])})
        for uid in (1, 22):
            fi, fs = fused.user_topk(uid, 5)
            xi, xs = xla.user_topk(uid, 5)
            np.testing.assert_array_equal(fi, xi)
            np.testing.assert_array_equal(fs, xs)

    def test_opt_out_env(self, monkeypatch, factor_pair):
        X, Y, seen = factor_pair
        monkeypatch.setenv("PIO_SERVE_KERNEL", "xla")
        srv = DeviceTopK(X, Y, seen)
        assert srv._kernel == "xla"
        monkeypatch.setenv("PIO_SERVE_KERNEL", "bogus")
        with pytest.raises(ValueError, match="PIO_SERVE_KERNEL"):
            DeviceTopK(X, Y, seen)

    @pytest.mark.slow
    def test_large_shape_multi_tile(self, monkeypatch):
        """A multi-tile catalog with a big k bucket (heavier interpret
        run, slow-marked)."""
        rng = np.random.default_rng(40)
        Q = int_factors(rng, (16, 16))
        Y = int_factors(rng, (1000, 16))
        sc = rng.integers(0, 1000, (12, 16)).astype(np.int32)
        sm = np.ones((12, 16), dtype=np.float32)
        vals, idx, _ = fused_gather_score_topk(
            jnp.asarray(Q), jnp.asarray(Y), seen_bits(sc, sm, 1000),
            k=64,
            n_items=997, mask_seen=True, interpret=True)
        wv, wi = xla_chain_topk(Q, Y, sc, sm, 64, 997)
        fin = np.isfinite(wv)
        np.testing.assert_array_equal(np.asarray(idx)[fin], wi[fin])
        np.testing.assert_array_equal(np.asarray(vals)[fin], wv[fin])
