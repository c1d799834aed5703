"""ops/seqrec.py tests: bucketing discipline, padded-vs-unpadded encoder
exactness, the mesh (ring/Ulysses) lane differential, and the training
gates (sampled-softmax loss decreases; learned next-item beats the
popularity baseline on a synthetic chain stream)."""

import numpy as np
import pytest

from predictionio_tpu.ops.als import PAD_MULTIPLE
from predictionio_tpu.ops.seqrec import (
    SeqRecParams,
    SequenceBucket,
    bucket_sequences,
    encode_bucket,
    encode_bucket_mesh,
    encode_users,
    init_theta,
    length_bucket,
    select_sp_kernel,
    train_seqrec,
)
from predictionio_tpu.parallel import data_parallel_mesh


def chain_sequences(n_users=60, n_items=40, min_len=3, max_len=14,
                    seed=0):
    """Synthetic next-item stream with a deterministic transition:
    item_{t+1} = (item_t + 1) % n_items — a strong signal a sequence
    model can learn and a set-based popularity baseline cannot."""
    rng = np.random.default_rng(seed)
    seqs = []
    for _ in range(n_users):
        start = int(rng.integers(0, n_items))
        n = int(rng.integers(min_len, max_len))
        seqs.append((start + np.arange(n)) % n_items)
    return seqs


class TestBucketing:
    def test_power_of_two_length_classes(self):
        assert length_bucket(1) == PAD_MULTIPLE
        assert length_bucket(PAD_MULTIPLE) == PAD_MULTIPLE
        assert length_bucket(PAD_MULTIPLE + 1) == 2 * PAD_MULTIPLE
        assert length_bucket(33) == 64

    def test_buckets_group_by_class_and_keep_rows(self):
        seqs = [np.arange(3), np.arange(10), np.arange(8), np.arange(20)]
        buckets = bucket_sequences(seqs)
        by_len = {b.seq_len: b for b in buckets}
        assert set(by_len) == {8, 16, 32}
        assert sorted(by_len[8].rows.tolist()) == [0, 2]
        assert by_len[16].rows.tolist() == [1]
        assert by_len[32].rows.tolist() == [3]
        # mask counts the true lengths
        assert by_len[16].mask.sum() == 10

    def test_truncation_keeps_last_items(self):
        seqs = [np.arange(100)]
        (b,) = bucket_sequences(seqs, max_len=8)
        assert b.seq_len == 8
        np.testing.assert_array_equal(b.ids[0], np.arange(92, 100))

    def test_empty_sequences_dropped(self):
        seqs = [np.arange(0), np.arange(4)]
        buckets = bucket_sequences(seqs)
        assert len(buckets) == 1
        assert buckets[0].rows.tolist() == [1]


class TestEncoderExactness:
    """The acceptance differential: padded/bucketed encoder output
    equals an unpadded per-sequence reference — the key-padding mask
    keeps pad slots out of every reduction."""

    # padded [B, L] vs unpadded [1, len]: two batch shapes are two
    # compiled programs, and a matmul owes no common last bit across
    # them (jax 0.9.0's CPU backend differs by ~2e-7 on these
    # unit-scale activations). The bound stays ~100x below what a
    # bf16 pass (2^-9 relative) would produce, so a lower-precision
    # encoder still fails. Same program and shapes — rows batched
    # together vs alone, below — stays bit-exact.
    SHAPE_TOL = dict(rtol=1e-5, atol=1e-6)

    def _setup(self, seed=1):
        rng = np.random.default_rng(seed)
        M = 30
        seqs = [rng.integers(0, M, size=n).astype(np.int32)
                for n in (3, 8, 12, 16, 1, 5, 7)]
        params = SeqRecParams(rank=16, n_layers=2, n_heads=4,
                              max_seq_len=16, seed=3)
        return M, seqs, params, init_theta(M, params)

    def test_bucketed_equals_unpadded_reference(self):
        M, seqs, params, theta = self._setup()
        U = encode_users(theta, bucket_sequences(seqs, max_len=16),
                         len(seqs), params)
        for i, s in enumerate(seqs):
            ref_bucket = SequenceBucket(
                np.array([0]), np.asarray(s, np.int32)[None, :],
                np.ones((1, len(s)), np.float32))
            ref = encode_bucket(theta, ref_bucket, params)[0]
            np.testing.assert_allclose(ref, U[i], **self.SHAPE_TOL)

    def test_batching_order_does_not_change_rows(self):
        """Rows batched together vs alone: identical vectors."""
        M, seqs, params, theta = self._setup(seed=2)
        same_len = [np.asarray(s, np.int32) for s in seqs
                    if length_bucket(len(s)) == 8]
        assert len(same_len) >= 2
        batched = encode_users(theta, bucket_sequences(same_len),
                               len(same_len), params)
        for i, s in enumerate(same_len):
            alone = encode_users(theta, bucket_sequences([s]), 1, params)
            np.testing.assert_array_equal(alone[0], batched[i])

    def test_userless_rows_stay_zero(self):
        M, seqs, params, theta = self._setup()
        U = encode_users(theta, bucket_sequences([np.arange(0),
                                                  np.arange(4)]),
                         2, params)
        assert not U[0].any()
        assert U[1].any()


class TestMeshLane:
    """The sequence-parallel kernels' differential: mesh encode matches
    the single-device encoder within documented tolerance (the ring /
    Ulysses programs reduce in a different order; 1e-5 absolute on
    unit-scale activations)."""

    TOL = dict(rtol=2e-4, atol=1e-5)

    def _setup(self, n_heads, seed=4):
        rng = np.random.default_rng(seed)
        M = 24
        seqs = [rng.integers(0, M, size=n).astype(np.int32)
                for n in (16, 16, 12, 9)]
        params = SeqRecParams(rank=16, n_layers=2, n_heads=n_heads,
                              max_seq_len=16, seed=5)
        return seqs, params, init_theta(M, params)

    @pytest.mark.parametrize("mode,heads", [("ring", 2), ("ulysses", 4)])
    def test_mesh_matches_single_device(self, mode, heads):
        seqs, params, theta = self._setup(n_heads=heads)
        params = SeqRecParams(**{**params.__dict__, "sp_mode": mode})
        mesh = data_parallel_mesh(4)
        (bucket,) = bucket_sequences(seqs, max_len=16)
        got = encode_bucket_mesh(theta, bucket, params, mesh)
        want = encode_bucket(theta, bucket, params)
        np.testing.assert_allclose(got, want, **self.TOL)

    def test_auto_picks_ulysses_when_heads_divide(self):
        mesh = data_parallel_mesh(4)
        assert select_sp_kernel(mesh, "data", 4, 16) == "ulysses"
        assert select_sp_kernel(mesh, "data", 2, 16) == "ring"
        # too short to shard: 8 tokens over 8 devices leaves 1 each
        mesh8 = data_parallel_mesh(8)
        assert select_sp_kernel(mesh8, "data", 8, 8) is None
        assert select_sp_kernel(mesh8, "data", 8, 16, "off") is None

    def test_forced_mode_raises_on_bad_shape(self):
        mesh = data_parallel_mesh(4)
        with pytest.raises(ValueError, match="ulysses"):
            select_sp_kernel(mesh, "data", 2, 16, "ulysses")
        with pytest.raises(ValueError, match="ring"):
            select_sp_kernel(mesh, "data", 2, 6, "ring")

    def test_auto_encode_users_on_mesh_matches(self):
        seqs, params, theta = self._setup(n_heads=4, seed=6)
        mesh = data_parallel_mesh(4)
        got = encode_users(theta, bucket_sequences(seqs, max_len=16),
                           len(seqs), params, mesh=mesh)
        want = encode_users(theta, bucket_sequences(seqs, max_len=16),
                            len(seqs), params)
        np.testing.assert_allclose(got, want, **self.TOL)


class TestTraining:
    def _train(self, seed=0, num_steps=150):
        seqs = chain_sequences(seed=seed)
        params = SeqRecParams(rank=16, n_layers=2, n_heads=2,
                              max_seq_len=16, num_steps=num_steps,
                              batch_size=32, n_negatives=32,
                              learning_rate=0.01, seed=seed)
        buckets = bucket_sequences(seqs, max_len=16)
        theta, losses = train_seqrec(buckets, 40, params)
        return seqs, params, buckets, theta, losses

    def test_sampled_softmax_loss_decreases(self):
        _, _, _, _, losses = self._train()
        assert np.isfinite(losses).all()
        assert losses[-10:].mean() < 0.5 * losses[:10].mean()

    def test_learned_next_item_beats_popularity(self):
        """hit@10 on the deterministic chain: the encoder must place
        each user's true next item in its top-10; popularity (with a
        near-uniform catalog) cannot."""
        seqs, params, buckets, theta, _ = self._train(seed=1)
        U = encode_users(theta, buckets, len(seqs), params)
        E = theta["item_emb"]
        M = E.shape[0]
        pop = np.bincount(np.concatenate(seqs), minlength=M)
        pop_top = set(np.argsort(-pop)[:10].tolist())
        hits = pop_hits = 0
        for u, seq in enumerate(seqs):
            nxt = int((seq[-1] + 1) % M)
            top = set(np.argsort(-(E @ U[u]))[:10].tolist())
            hits += nxt in top
            pop_hits += nxt in pop_top
        assert hits / len(seqs) > 0.8
        assert hits > pop_hits

    def test_deterministic_given_seed(self):
        _, _, _, t1, l1 = self._train(seed=2, num_steps=30)
        _, _, _, t2, l2 = self._train(seed=2, num_steps=30)
        np.testing.assert_array_equal(l1, l2)
        for k in t1:
            np.testing.assert_array_equal(t1[k], t2[k])

    def test_empty_buckets_raise(self):
        with pytest.raises(ValueError, match="no non-empty"):
            train_seqrec([], 10, SeqRecParams(rank=8))

    def test_rank_heads_divisibility_enforced(self):
        with pytest.raises(ValueError, match="divisible"):
            init_theta(10, SeqRecParams(rank=10, n_heads=4))
