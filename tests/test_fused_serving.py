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
    fused_gather_score_topk,
    pack_seen_ids,
)
from predictionio_tpu.ops.quantize import (
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
        vals, idx = fused_gather_score_topk(
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
        vals, idx = fused_gather_score_topk(
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
        vals, idx = fused_gather_score_topk(
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
        vals, _ = fused_gather_score_topk(
            jnp.asarray(Q), jnp.asarray(Y), seen_bits(sc, sm, 10), k=4,
            n_items=10, mask_seen=True, interpret=True)
        assert (np.asarray(vals) == -np.inf).all()

    def test_continuous_data_allclose(self):
        rng = np.random.default_rng(7)
        Q = rng.normal(size=(6, 8)).astype(np.float32)
        Y = rng.normal(size=(150, 8)).astype(np.float32)
        vals, idx = fused_gather_score_topk(
            jnp.asarray(Q), jnp.asarray(Y), k=10,
            n_items=150, mask_seen=False, interpret=True)
        wv, wi = xla_chain_topk(Q, Y, None, None, 10, 150)
        np.testing.assert_allclose(np.asarray(vals), wv, rtol=1e-5)
        for b in range(6):
            assert set(np.asarray(idx)[b].tolist()) == \
                set(wi[b].tolist())


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
        vals, idx = fused_gather_score_topk(
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
        vals, _ = fused_gather_score_topk(
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
        serve-time-compile contract the bench asserts end to end)."""
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
        vals, idx = fused_gather_score_topk(
            jnp.asarray(Q), jnp.asarray(Y), seen_bits(sc, sm, 1000),
            k=64,
            n_items=997, mask_seen=True, interpret=True)
        wv, wi = xla_chain_topk(Q, Y, sc, sm, 64, 997)
        fin = np.isfinite(wv)
        np.testing.assert_array_equal(np.asarray(idx)[fin], wi[fin])
        np.testing.assert_array_equal(np.asarray(vals)[fin], wv[fin])
