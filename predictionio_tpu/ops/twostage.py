"""Two-stage serving: fused retrieval + re-rank as ONE device program.

The canonical production shape (ROADMAP item 5): ALS retrieves N
candidates from the full catalog (stage 1 — cheap, scales to the
catalog, per-shard on a mesh with the log-tree ppermute merge), and the
seqrec encoder re-ranks ONLY those N with the user's live sequence
state (stage 2 — expensive per item, so it must never see the
catalog). The handoff is the whole point: the N candidate positions
never leave HBM — the same jitted program gathers the candidates'
stage-2 item embeddings, scores them against the encoded user state,
applies the seen mask exactly once, and takes the final top-k. One
dispatch per query batch, one packed fetch, no host round trip of
candidate ids or embeddings (asserted by the flight recorder: a served
batch records one ``two``-lane dispatch, not a ``users`` + a gather).

:class:`TwoStageTopK` extends :class:`~predictionio_tpu.ops.serving.
DeviceTopK` — the stage-1 store IS the parent store (same sharding,
precision, fused-kernel and seen-mask policies), and the two-stage
lane rides every existing serving discipline:

* programs are cached per ``(k-bucket, N-bucket)`` and dispatched per
  ``(uid-bucket, N-bucket, k-bucket)`` through the PR-10
  :class:`~predictionio_tpu.ops.serving.BatchDispatcher` (its own
  micro-batch lane, ``pio-microbatch-two``);
* the N-bucket joins the ``ops/aot.py`` ladder — ``aot_plan`` grows
  ``("two", kb, nb, bb)`` entries, so after ``warmup()`` steady state
  compiles nothing;
* both stages fold in online: :meth:`DeviceTopK.patch_users` keeps
  patching the stage-1 ALS rows, :meth:`TwoStageTopK.patch_seq_users`
  patches the stage-2 encoded user state, and both grow the store
  along the same bucket ladder under the same ``_store_lock`` (a
  concurrent query sees either the whole old store or the whole new
  one).

Tie-break discipline: stage 1 retrieves WITHOUT the seen mask, the
candidate run is re-sorted ascending by store position before stage 2,
so ``lax.top_k``'s lowest-ordinal tie-break equals the lowest-position
rule of a brute-force full-catalog re-rank — at N=catalog the two are
bit-identical (the differential gate in ``tests/test_twostage.py``).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from predictionio_tpu.ops.aot import lower_compile
from predictionio_tpu.ops.serving import (
    BatchLane,
    DeviceTopK,
    _bucket,
    _deliver,
    _gather_rows_f32,
    _pack,
    _Pending,
    _scatter_quant_rows,
    _scatter_rows,
    _score_einsum,
    _serve_precision_explicit,
    _serve_shards_env,
    _sharded_score_topk,
    _table_sig,
    foldin_enabled,
    validate_serving_policy,
)
from predictionio_tpu.utils import device_telemetry as _dtel
from predictionio_tpu.utils.tracing import span as _trace_span

DEFAULT_CANDIDATES = 128


def _candidates_env() -> int:
    import os

    raw = os.environ.get("PIO_TWOSTAGE_N", "").strip()
    if raw:
        try:
            return max(1, int(raw))
        except ValueError:
            raise ValueError(
                f"PIO_TWOSTAGE_N={raw!r} is not an integer")
    return DEFAULT_CANDIDATES


def _dispatch_two_group(srv: "TwoStageTopK",
                        group: List[_Pending]) -> None:
    """Per-user two-stage requests -> one ``twos_topk`` dispatch (the
    batch pads to its power-of-two uid bucket inside ``twos_topk``;
    every ladder bucket is AOT-precompiled or jit-warmed, so arbitrary
    group sizes never pay a serve-time compile)."""
    kmax = max(it.k for it in group)
    uids = np.asarray([it.payload for it in group], dtype=np.int64)
    _deliver(group, *srv.twos_topk(uids, kmax))


def _twostage_rerank(E, U, uids, vals1, pos, sbq, *, kb: int,
                     mode: str, mask_seen: bool, pos_ids=None,
                     rounds=None):
    """Stage 2, shared by every stage-1 lane (XLA / fused / sharded):
    candidate gather -> re-rank score -> ONE seen mask -> final top-k,
    all inside the caller's jitted program (the candidates never leave
    HBM).

    ``vals1``/``pos`` are the stage-1 run ([B, nb] scores descending +
    store positions); ``sbq`` the query users' rows of the packed seen
    bitmap over store POSITIONS. Candidates re-sort ascending by ITEM
    ID first (``pos_ids`` maps positions to ids on density-permuted
    stores; identity otherwise) so ``lax.top_k``'s lowest-ordinal tie-break
    equals the brute-force lowest-item-id rule — bit-exact at
    N=catalog on every lane, including sharded."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    key = pos if pos_ids is None else jnp.take(pos_ids, pos, axis=0)
    order = jnp.argsort(key, axis=-1)
    pos = jnp.take_along_axis(pos, order, axis=-1)
    vals1 = jnp.take_along_axis(vals1, order, axis=-1)
    # jnp.take clamps out-of-range positions (merge pads); their rows
    # score garbage but vals1 there is -inf, masked below
    C = _gather_rows_f32(E, pos, mode=mode)          # [B, nb, R2]
    S = _gather_rows_f32(U, uids, mode=mode)         # [B, R2]
    s2 = _score_einsum("bnr,br->bn", C, S, mode=mode)
    # stage-1 invalidity (padded positions, short catalogs, merge
    # fill) carries over: a candidate stage 1 scored -inf stays -inf
    s2 = jnp.where(jnp.isfinite(vals1), s2, -jnp.inf)
    if mask_seen:
        # the seen mask applies EXACTLY once, here — stage 1 retrieves
        # unmasked so the candidate run is the same one a brute-force
        # re-rank would score
        # candidate position p = bit (p & 31) of word (p >> 5); merge
        # pads may sit out of range — clamp the word index (their s2
        # is already -inf)
        word = jnp.take_along_axis(
            sbq, jnp.clip(pos >> 5, 0, sbq.shape[-1] - 1), axis=-1)
        s2 = jnp.where((jnp.right_shift(word, pos & 31) & 1) > 0,
                       -jnp.inf, s2)
    out_vals, sel = lax.top_k(s2, kb)
    out_pos = jnp.take_along_axis(pos, sel, axis=-1)
    with jax.named_scope("pack"):
        return _pack(out_vals, out_pos, rounds)


class TwoStageTopK(DeviceTopK):
    """Fused retrieval + re-rank device store over TWO factor stores.

    Stage 1 is the inherited :class:`DeviceTopK` store
    (``user_factors``/``item_factors``, the ALS retrieval model,
    possibly mesh-sharded in the density-aware item order). Stage 2
    holds the re-ranker's tables resident next to it:
    ``seq_item_vectors`` (item embeddings, re-placed into the SAME
    store-position order as the stage-1 item table so candidate
    positions index both) and ``seq_user_vectors`` (the encoded user
    states, row-aligned and capacity-grown with the stage-1 user
    table). All four tables follow the store's one precision policy
    (fp32 / bf16 / int8 with per-row scales).

    ``candidates`` (or ``PIO_TWOSTAGE_N``, default 128) sets N — the
    stage-1 run length stage 2 re-ranks. N is bucketed like k, so the
    dispatched program family is ``(uid-bucket, N-bucket, k-bucket)``.

    Every inherited lane (``user_topk``/``users_topk``/``items_topk``,
    patching, AOT ladder, telemetry) still serves — two-stage queries
    go through :meth:`two_topk` / :meth:`twos_topk`.
    """

    def __init__(self, user_factors, item_factors, seq_user_vectors,
                 seq_item_vectors,
                 seen: Optional[Dict[int, np.ndarray]] = None,
                 candidates: Optional[int] = None,
                 n_users: Optional[int] = None,
                 n_items: Optional[int] = None,
                 microbatch: Optional[bool] = None,
                 item_layout=None,
                 shards: Optional[int] = None):
        super().__init__(user_factors, item_factors, seen,
                         n_users=n_users, n_items=n_items,
                         microbatch=microbatch, item_layout=item_layout,
                         shards=shards)
        self._two_batcher: Optional[BatchLane] = None
        if self._dispatcher is not None:
            self._two_batcher = self._dispatcher.add_lane(
                "pio-microbatch-two", max_batch=256,
                dispatch_fn=_dispatch_two_group)
        n_cand = int(candidates) if candidates is not None \
            else _candidates_env()
        if n_cand < 1:
            raise ValueError(
                f"two-stage candidate count must be >= 1, got {n_cand}")
        self._candidates = n_cand
        self._n_bucket = min(_bucket(max(n_cand, 16)), self.n_items)
        with self._store_lock:
            with _trace_span("store.upload"):
                import jax

                self._E, self._U = jax.block_until_ready(
                    (self._prep_stage2_items(seq_item_vectors),
                     self._prep_stage2_users(seq_user_vectors)))
            # position -> item id (i32, invalid positions sort last):
            # the re-rank sorts candidates by id so tie-break matches
            # the brute-force rule even on a density-permuted store.
            # The item layout is fixed for the store's lifetime, so the
            # programs close over it.
            if self._perm_np is not None:
                import jax.numpy as jnp

                ids = np.where(self._perm_np >= 0, self._perm_np,
                               np.iinfo(np.int32).max).astype(np.int32)
                self._pos_ids = self._replicate_stage2(jnp.asarray(ids))
            else:
                self._pos_ids = None
        self._two_programs: Dict[Tuple[int, int], object] = {}

    # -- stage-2 table preparation ----------------------------------------

    def _align_rows_to_positions(self, a: np.ndarray, n_pos: int,
                                 fill) -> np.ndarray:
        """Re-order an item-id-indexed table into the stage-1 store's
        POSITION order (identity without a density layout), padding to
        ``n_pos`` rows with ``fill`` — so one candidate position indexes
        both stages' item tables."""
        out = np.full((n_pos,) + a.shape[1:], fill, dtype=a.dtype)
        if self._perm_np is not None:
            real = self._perm_np >= 0
            out[real] = a[self._perm_np[real]]
        else:
            m = min(n_pos, a.shape[0])
            out[:m] = a[:m]
        return out

    def _cast_stage2(self, arr_np: np.ndarray, scale_np:
                     Optional[np.ndarray]):
        """Host rows -> a device table in the store's precision policy
        (the ctor's fp32/bf16/int8 rule applied to a stage-2 table),
        replicated on the stage-1 mesh when there is one."""
        import jax.numpy as jnp

        from predictionio_tpu.ops.quantize import (
            QuantFactors,
            quantize_rows_int8,
        )

        if scale_np is not None:
            # input arrived pre-quantized: keep its scales verbatim
            return QuantFactors(
                self._replicate_stage2(jnp.asarray(arr_np)),
                self._replicate_stage2(
                    jnp.asarray(scale_np).astype(jnp.float32)))
        arr = jnp.asarray(arr_np, dtype=jnp.float32)
        if self._mode == "int8":
            q = quantize_rows_int8(arr)
            return QuantFactors(self._replicate_stage2(q.data),
                                self._replicate_stage2(q.scale))
        if self._mode == "bf16":
            arr = arr.astype(jnp.bfloat16)
        return self._replicate_stage2(arr)

    def _replicate_stage2(self, arr):
        """ndim-general twin of ``_replicate_like_factors`` (stage-2
        scales are 1-D): pin replicated on whatever mesh the stage-1
        store committed to, else leave as created."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = None
        if self._shard is not None:
            mesh = self._shard[0]
        else:
            sh = getattr(self._X, "sharding", None)
            if isinstance(sh, NamedSharding) and sh.mesh.devices.size > 1:
                mesh = sh.mesh
        if mesh is None:
            return arr
        return jax.device_put(
            arr, NamedSharding(mesh, P(*([None] * arr.ndim))))

    def _prep_stage2_items(self, E):
        """Stage-2 item embeddings -> position order, store precision,
        replicated. Caller holds ``_store_lock``."""
        from predictionio_tpu.ops.quantize import is_quantized

        n_pos = int(self._Y.shape[0])
        if is_quantized(E):
            data, scale = np.asarray(E.data), np.asarray(E.scale)
        else:
            data, scale = np.asarray(E), None
        if data.ndim != 2:
            raise ValueError(
                f"stage-2 item table must be [items, rank], got shape "
                f"{data.shape}")
        if data.shape[0] < self.n_items:
            raise ValueError(
                f"stage-2 item table covers {data.shape[0]} items but "
                f"the stage-1 catalog has {self.n_items}: the two "
                "stages must be trained against one shared item map")
        aligned = self._align_rows_to_positions(data, n_pos, 0)
        if scale is not None:
            scale = self._align_rows_to_positions(scale, n_pos, 1.0)
        return self._cast_stage2(aligned, scale)

    def _prep_stage2_users(self, U):
        """Stage-2 encoded user states -> stage-1 user capacity (rows
        past ``n_users`` zero until folded in), store precision,
        replicated. Caller holds ``_store_lock``."""
        from predictionio_tpu.ops.quantize import is_quantized

        cap = int(self._X.shape[0])
        if is_quantized(U):
            data, scale = np.asarray(U.data), np.asarray(U.scale)
        else:
            data, scale = np.asarray(U), None
        if data.ndim != 2:
            raise ValueError(
                f"stage-2 user table must be [users, rank], got shape "
                f"{data.shape}")
        if data.shape[0] < self.n_users:
            raise ValueError(
                f"stage-2 user table covers {data.shape[0]} users but "
                f"the stage-1 store serves {self.n_users}: the two "
                "stages must be trained against one shared user map")
        padded = np.zeros((cap,) + data.shape[1:], dtype=data.dtype)
        padded[:min(cap, data.shape[0])] = data[:cap]
        if scale is not None:
            s = np.ones((cap,), dtype=scale.dtype)
            s[:min(cap, len(scale))] = scale[:cap]
            scale = s
        return self._cast_stage2(padded, scale)

    def _grow_user_tables(self, tables: Dict[str, Any],
                          new_cap: int) -> Dict[str, Any]:
        """The parent's growth plus the stage-2 user table, which
        always spans the stage-1 capacity (grown rows dequantize to
        zero until their encoded state folds in)."""
        import jax.numpy as jnp

        from predictionio_tpu.ops.quantize import (
            QuantFactors,
            is_quantized,
        )

        grown = super()._grow_user_tables(tables, new_cap)
        U = tables["U"]
        pad = new_cap - int(U.shape[0])
        if is_quantized(U):
            grown["U"] = QuantFactors(
                self._replicate_stage2(
                    jnp.pad(U.data, ((0, pad), (0, 0)))),
                self._replicate_stage2(
                    jnp.pad(U.scale, ((0, pad),), constant_values=1)))
        else:
            grown["U"] = self._replicate_stage2(
                jnp.pad(U, ((0, pad), (0, 0))))
        return grown

    def _publish_user_tables_locked(self, tables: Dict[str, Any]) -> None:
        super()._publish_user_tables_locked(tables)
        self._U = tables["U"]

    # -- compilation -------------------------------------------------------

    def _nb_for(self, kb: int) -> int:
        """The N bucket a k-bucket dispatch retrieves: at least the
        configured candidate bucket, at least kb (stage 2 cannot rank
        more winners than stage 1 hands over), at most the catalog."""
        return min(max(self._n_bucket, kb), self.n_items)

    def _two_program(self, kb: int, nb: int):
        """The fused two-stage program for one (k, N) bucket pair:
        stage-1 retrieval (per the store's kernel/shard lane, UNMASKED)
        and the candidate re-rank lower into ONE jitted program.
        Shape-polymorphic over the uid bucket; the AOT ladder pins each
        bucket's executable."""
        prog = self._two_programs.get((kb, nb))
        if prog is not None:
            return prog
        import jax
        import jax.numpy as jnp

        mode, mask_seen = self._mode, self._mask_seen
        n_items = self.n_items
        pos_ids = self._pos_ids
        if self._shard is not None:
            mesh, axis, _ = self._shard
            fused = self._kernel == "fused"
            interpret = self._interpret

            @jax.jit
            def two_topk(X, Y, valid, E, U, sb, uids):
                with jax.named_scope("stage1"):
                    with jax.named_scope("gather_q"):
                        Q = _gather_rows_f32(X, uids, mode=mode)
                    with jax.named_scope("seen_rows"):
                        sbq = jnp.take(sb, uids, axis=0)
                    with jax.named_scope("topk"):
                        vals1, pos, rounds = _sharded_score_topk(
                            Y, valid, Q, sbq, k=nb, mask_seen=False,
                            mode=mode, mesh=mesh, axis=axis, fused=fused,
                            interpret=interpret)
                with jax.named_scope("rerank"):
                    return _twostage_rerank(E, U, uids, vals1, pos, sbq,
                                            kb=kb, mode=mode,
                                            mask_seen=mask_seen,
                                            pos_ids=pos_ids,
                                            rounds=rounds)
        elif self._kernel == "fused":
            from predictionio_tpu.ops.als_pallas import (
                fused_gather_score_topk,
            )

            interpret = self._interpret

            @jax.jit
            def two_topk(X, Y, E, U, sb, uids):
                with jax.named_scope("stage1"):
                    with jax.named_scope("gather_q"):
                        Q = _gather_rows_f32(X, uids, mode=mode)
                    with jax.named_scope("topk"):
                        vals1, pos, rounds = fused_gather_score_topk(
                            Q, Y, k=nb, n_items=n_items, mask_seen=False,
                            interpret=interpret)
                with jax.named_scope("seen_rows"):
                    sbq = jnp.take(sb, uids, axis=0)
                with jax.named_scope("rerank"):
                    return _twostage_rerank(E, U, uids, vals1, pos, sbq,
                                            kb=kb, mode=mode,
                                            mask_seen=mask_seen,
                                            pos_ids=pos_ids,
                                            rounds=rounds)
        else:
            n_rows = int(self._Y.shape[0])

            @jax.jit
            def two_topk(X, Y, E, U, sb, uids):
                from jax import lax

                with jax.named_scope("stage1"):
                    with jax.named_scope("gather_q"):
                        Q = _gather_rows_f32(X, uids, mode=mode)
                    with jax.named_scope("topk"):
                        scores = _score_einsum("mr,br->bm", Y, Q,
                                               mode=mode)
                        if n_rows > n_items:
                            pad_ok = jnp.arange(n_rows)[None, :] < n_items
                            scores = jnp.where(pad_ok, scores, -jnp.inf)
                        vals1, pos = lax.top_k(scores, nb)
                with jax.named_scope("seen_rows"):
                    sbq = jnp.take(sb, uids, axis=0)
                with jax.named_scope("rerank"):
                    return _twostage_rerank(E, U, uids, vals1, pos, sbq,
                                            kb=kb, mode=mode,
                                            mask_seen=mask_seen,
                                            pos_ids=pos_ids)

        self._two_programs[(kb, nb)] = two_topk
        return two_topk

    def _two_args(self, uids) -> Tuple:
        """The two-stage program's argument tuple for the live store
        (sharded programs additionally take the validity row)."""
        if self._shard is not None:
            return (self._X, self._Y, self._valid, self._E, self._U,
                    self._seen_bits, uids)
        return (self._X, self._Y, self._E, self._U, self._seen_bits,
                uids)

    # -- AOT bucket ladder -------------------------------------------------

    def _store_tables_locked(self) -> Dict[str, Any]:
        return dict(super()._store_tables_locked(), E=self._E, U=self._U)

    def _store_sig(self, tables: Dict[str, Any]) -> Tuple:
        return super()._store_sig(tables) + (
            _table_sig(tables["E"]), _table_sig(tables["U"]),
            self._n_bucket)

    def aot_plan(self, max_k: int = 128,
                 batch_sizes: Tuple[int, ...] = ()) -> List[Tuple]:
        """The parent ladder plus one ``("two", kb, nb, bb)`` program
        per (k bucket, uid bucket) — N joins the ladder, so steady
        state two-stage traffic compiles nothing."""
        plan = super().aot_plan(max_k=max_k, batch_sizes=batch_sizes)
        ks = sorted({e[1] for e in plan if e[0] == "user"})
        buckets = sorted({e[2] for e in plan if e[0] == "users"})
        for kb in ks:
            for bb in buckets:
                plan.append(("two", kb, self._nb_for(kb), bb))
        return plan

    def _aot_lower_entry(self, entry: Tuple, tables: Dict[str, Any]):
        if entry[0] != "two":
            return super()._aot_lower_entry(entry, tables)
        import jax
        import jax.numpy as jnp

        _, kb, nb, bb = entry
        t = tables
        if self._shard is not None:
            pre = (t["X"], t["Y"], t["valid"], t["E"], t["U"],
                   t["seen_bits"])
        else:
            pre = (t["X"], t["Y"], t["E"], t["U"], t["seen_bits"])
        return lower_compile(self._two_program(kb, nb), *pre,
                             jax.ShapeDtypeStruct((bb,), jnp.int32))

    def _warm_entry(self, entry: Tuple) -> None:
        if entry[0] != "two":
            return super()._warm_entry(entry)
        _, kb, nb, bb = entry
        self.twos_topk(np.zeros(bb, dtype=np.int64), kb)

    def warmup(self, max_k: int = 128,
               batch_sizes: Tuple[int, ...] = ()) -> Dict[str, int]:
        stats = super().warmup(max_k=max_k, batch_sizes=batch_sizes)
        # one sacrificial two-stage query pins the runtime dispatch
        # caches for the fused lane too (parent did user/users/items)
        kmin = min(16, self.n_items)
        self.twos_topk(np.zeros(8, dtype=np.int64), kmin)
        return stats

    # -- serving -----------------------------------------------------------

    def two_topk(self, uid: int, k: int) -> Tuple[np.ndarray,
                                                  np.ndarray]:
        """(item indices, scores) for one user through the fused
        retrieval + re-rank program, descending by the STAGE-2 score;
        seen items are masked once on device. Concurrent callers share
        one dispatch via the ``pio-microbatch-two`` lane."""
        with _trace_span("device.two_topk",
                         attributes={"k": int(k)}) as sp:
            if self._two_batcher is not None:
                return self._two_batcher.submit(int(uid), int(k),
                                                span=sp)
            return self._two_topk_direct(uid, k)

    def _two_topk_direct(self, uid: int,
                         k: int) -> Tuple[np.ndarray, np.ndarray]:
        idx, scores = self.twos_topk(
            np.asarray([int(uid)], dtype=np.int64), k)
        idx, scores = idx[0], scores[0]
        valid = np.isfinite(scores)
        return idx[valid], scores[valid]

    def twos_topk(self, uids, k: int) -> Tuple[np.ndarray, np.ndarray]:
        """Batched fused two-stage top-k: ONE device dispatch and ONE
        packed fetch for the whole batch — retrieval, candidate gather,
        re-rank, seen mask and final top-k never surface on host.

        Returns ``(idx [B, k] int32, scores [B, k] float32)`` rows
        descending by re-rank score; rows may contain -inf scores past
        the valid candidates (callers filter per row)."""
        uids = np.asarray(uids, dtype=np.int32)
        n = len(uids)
        with _trace_span("device.twos_topk",
                         attributes={"batch": int(n), "k": int(k)}):
            with _dtel.stage("formUs", "batch.form"):
                bb = _bucket(max(n, 1), lo=8)
                padded = np.zeros(bb, dtype=np.int32)
                padded[:n] = uids
                kb = min(_bucket(k), self.n_items)
                nb = self._nb_for(kb)
            out = self._dispatch_entry(
                ("two", kb, nb, bb),
                lambda: self._two_program(kb, nb),
                lambda: self._two_args(padded),
                batch=n, bucket=bb)
            return self._fetch(out, kb, np.s_[:n, :k])

    def stats(self) -> Dict[str, Dict[str, int]]:
        out = super().stats()
        if self._two_batcher is not None:
            out["two"] = self._two_batcher.stats()
        return out

    # -- accounting --------------------------------------------------------

    def memory_report(self) -> Dict[str, Any]:
        from predictionio_tpu.ops.quantize import is_quantized

        report = super().memory_report()
        with self._store_lock:
            E, U = self._E, self._U

        def comp(f) -> Dict[str, Any]:
            if is_quantized(f):
                return {"bytes": int(f.data.nbytes),
                        "scaleBytes": int(f.scale.nbytes),
                        "dtype": str(f.data.dtype),
                        "scaleDtype": str(f.scale.dtype),
                        "shape": [int(d) for d in f.data.shape]}
            return {"bytes": int(f.nbytes), "scaleBytes": 0,
                    "dtype": str(f.dtype),
                    "shape": [int(d) for d in f.shape]}

        extra = {"stage2ItemVectors": comp(E),
                 "stage2UserVectors": comp(U)}
        report["components"].update(extra)
        report["totalBytes"] += sum(c["bytes"] + c["scaleBytes"]
                                    for c in extra.values())
        report["twoStage"] = {"candidates": self._candidates,
                              "nBucket": self._n_bucket}
        return report

    # -- live store patching (online fold-in, both stages) -----------------

    @property
    def seq_item_factors(self):
        """The stage-2 item embedding table in ITEM-ID order, fp32 —
        what the re-ranker's fold-in re-encode reads. Dequantized /
        de-permuted per access, same tradeoff as
        :attr:`DeviceTopK.item_factors`."""
        from predictionio_tpu.ops.quantize import (
            dequantize_rows,
            is_quantized,
        )

        with self._store_lock:
            E = self._E
            inv = self._inv_np
        Ef = dequantize_rows(E) if is_quantized(E) else E
        import jax.numpy as jnp

        Ef = jnp.asarray(Ef).astype(jnp.float32)
        if inv is not None:
            return jnp.take(Ef, jnp.asarray(inv), axis=0)
        return Ef[:self.n_items]

    def patch_seq_users(self, uids, vectors,
                        seen_items: Optional[Dict[int, np.ndarray]]
                        = None) -> None:
        """Scatter freshly RE-ENCODED user states into the live
        stage-2 table — the re-ranker's fold-in write path (the PR-14
        re-encode hook pointed at stage 2). Same atomicity contract as
        :meth:`patch_users`: every reference swaps under the one
        ``_store_lock`` the dispatch paths snapshot under.

        A uid past the current capacity grows BOTH stores through the
        one growth path (:meth:`_reserve_users`; the new user's
        retrieval row stays zero until its ALS half-step folds in), so
        the two tables can never disagree about capacity."""
        from predictionio_tpu.ops.quantize import (
            QuantFactors,
            is_quantized,
            quantize_rows_int8_np,
        )

        uids = np.asarray(uids, dtype=np.int64)
        vectors = np.asarray(vectors, dtype=np.float32)
        if vectors.ndim != 2 or len(uids) != vectors.shape[0]:
            raise ValueError(
                f"patch_seq_users: {len(uids)} uids vs vectors "
                f"{vectors.shape}")
        if not len(uids):
            return
        if uids.min() < 0:
            raise ValueError("patch_seq_users: negative user index")
        rank2 = int(self._U.shape[1])  # fixed for the store's lifetime
        if vectors.shape[1] != rank2:
            raise ValueError(
                f"patch_seq_users: vectors rank {vectors.shape[1]} "
                f"vs stage-2 store rank {rank2}")
        seen_tr = self._translate_seen(seen_items) if seen_items \
            else seen_items
        seen_prep = self._prep_seen(seen_tr) \
            if self._mask_seen and seen_tr else None
        needed = int(uids.max()) + 1
        with self._write_lock:
            self._reserve_users(needed)
            with self._store_lock:
                if seen_prep is not None:
                    self._seen_bits = _scatter_rows(self._seen_bits,
                                                    *seen_prep)
                U = self._U
                if is_quantized(U):
                    q = quantize_rows_int8_np(vectors)
                    self._U = QuantFactors(*_scatter_quant_rows(
                        U.data, U.scale, uids, q.data, q.scale))
                else:
                    self._U = _scatter_rows(U, uids, vectors)
                self.n_users = max(self.n_users, needed)

    # -- serving facets ----------------------------------------------------

    def two_facet(self) -> "_TwoStageFacet":
        """The device-server handle the RETRIEVAL model serves through
        in a fused deployment: per-user queries route to the two-stage
        lane, everything else (fold-in writes, warmup, accounting)
        stays the stage-1 surface."""
        return _TwoStageFacet(self)

    def seq_facet(self) -> "_SeqStoreFacet":
        """The device-server handle the RE-RANK model holds in a fused
        deployment: its fold-in writes land in the stage-2 table, its
        queries route to the shared two-stage lane, and its warmup is a
        no-op (the store's one ladder warms once)."""
        return _SeqStoreFacet(self)


class _TwoStageFacet:
    """DeviceTopK-shaped view of a :class:`TwoStageTopK` for the
    retrieval model: ``user_topk``/``users_topk`` dispatch the FUSED
    two-stage program, so the recommendation template's serving helpers
    (blacklists, categories, batch grouping) run unmodified on the
    two-stage path; the write/ops surface delegates to stage 1."""

    def __init__(self, store: TwoStageTopK):
        self.store = store

    def user_topk(self, uid: int, k: int):
        return self.store.two_topk(uid, k)

    def users_topk(self, uids, k: int):
        return self.store.twos_topk(uids, k)

    def items_topk(self, idxs, k: int):
        return self.store.items_topk(idxs, k)

    def warmup(self, *a, **kw):
        return self.store.warmup(*a, **kw)

    def patch_users(self, uids, factors, seen_items=None):
        return self.store.patch_users(uids, factors,
                                      seen_items=seen_items)

    @property
    def growable(self) -> bool:
        return self.store.growable

    @property
    def item_factors(self):
        return self.store.item_factors

    @property
    def item_layout(self):
        return self.store.item_layout

    @property
    def shard_count(self) -> int:
        return self.store.shard_count

    @property
    def user_capacity(self) -> int:
        return self.store.user_capacity

    def stats(self):
        return self.store.stats()

    def memory_report(self):
        return self.store.memory_report()

    def ladder_report(self):
        return self.store.ladder_report()

    def close(self) -> None:
        self.store.close()


class _SeqStoreFacet:
    """DeviceTopK-shaped view of a :class:`TwoStageTopK` for the
    re-rank model: fold-in writes patch the STAGE-2 user table,
    ``item_factors`` hands back the stage-2 embeddings the re-encode
    reads, queries route to the shared fused lane, and lifecycle ops
    are no-ops (the one store warms/closes through the stage-1 facet).
    """

    def __init__(self, store: TwoStageTopK):
        self.store = store

    def user_topk(self, uid: int, k: int):
        return self.store.two_topk(uid, k)

    def users_topk(self, uids, k: int):
        return self.store.twos_topk(uids, k)

    def items_topk(self, idxs, k: int):
        return self.store.items_topk(idxs, k)

    def warmup(self, *a, **kw):
        return {}

    def patch_users(self, uids, factors, seen_items=None):
        return self.store.patch_seq_users(uids, factors,
                                          seen_items=seen_items)

    @property
    def growable(self) -> bool:
        return True

    @property
    def item_factors(self):
        return self.store.seq_item_factors

    @property
    def user_capacity(self) -> int:
        return self.store.user_capacity

    def stats(self):
        return {}

    def memory_report(self):
        return {"totalBytes": 0, "components": {},
                "sharedWith": "twoStage"}

    def close(self) -> None:  # the stage-1 facet owns the dispatcher
        return None


def build_two_stage_store(retrieval_model, rerank_model,
                          candidates: Optional[int] = None
                          ) -> TwoStageTopK:
    """Validate a two-model deployment and build its ONE fused store.

    ``retrieval_model`` must expose the ALS-shaped surface
    (``user_factors``/``item_factors``/``user_map``/``item_map``/
    ``seen``); ``rerank_model`` the seqrec-shaped one
    (``user_vectors``/``item_vectors``). Loud policy errors — the
    table-driven :func:`~predictionio_tpu.ops.serving.
    validate_serving_policy` ``two_stage`` row rejects an explicit host
    backend, and a fold-in deployment whose re-ranker cannot re-encode
    (no ``fold_in_rows``) is refused here rather than half-binding."""
    import os

    for attr in ("user_factors", "item_factors", "user_map",
                 "item_map"):
        if getattr(retrieval_model, attr, None) is None:
            raise ValueError(
                "two-stage serving: the FIRST algorithm must be the "
                "retrieval stage (ALS-shaped: user_factors/item_factors"
                f"/user_map/item_map); {type(retrieval_model).__name__} "
                f"has no {attr}")
    for attr in ("user_vectors", "item_vectors"):
        if getattr(rerank_model, attr, None) is None:
            raise ValueError(
                "two-stage serving: the LAST algorithm must be the "
                "re-rank stage (seqrec-shaped: user_vectors/"
                f"item_vectors); {type(rerank_model).__name__} has no "
                f"{attr}")
    if len(retrieval_model.item_map) != len(rerank_model.item_map):
        raise ValueError(
            "two-stage serving: the stages disagree about the catalog "
            f"({len(retrieval_model.item_map)} vs "
            f"{len(rerank_model.item_map)} items) — both algorithms "
            "must train from one Preparator with one shared item map")
    if len(retrieval_model.user_map) != len(rerank_model.user_map):
        raise ValueError(
            "two-stage serving: the stages disagree about the users "
            f"({len(retrieval_model.user_map)} vs "
            f"{len(rerank_model.user_map)}) — both algorithms must "
            "train from one Preparator with one shared user map")
    host_capable = not (
        hasattr(retrieval_model.user_factors, "sharding")
        or hasattr(retrieval_model.item_factors, "sharding"))
    backend = os.environ.get("PIO_SERVING_BACKEND", "auto").lower()
    validate_serving_policy(
        backend, host_capable=host_capable,
        explicit_precision=_serve_precision_explicit(),
        foldin=foldin_enabled(), sharded=_serve_shards_env() > 1,
        two_stage=True)
    if foldin_enabled() and not callable(
            getattr(rerank_model, "fold_in_rows", None)):
        raise ValueError(
            "two-stage serving with PIO_FOLDIN=on needs a re-ranker "
            "that can re-encode folded-in users (fold_in_rows); "
            f"{type(rerank_model).__name__} has none — disable fold-in "
            "or use a re-rank model with an online encode hook")
    return TwoStageTopK(
        retrieval_model.user_factors, retrieval_model.item_factors,
        rerank_model.user_vectors, rerank_model.item_vectors,
        seen=getattr(retrieval_model, "seen", None),
        candidates=candidates,
        n_users=getattr(retrieval_model, "n_users", None),
        n_items=getattr(retrieval_model, "n_items", None),
        item_layout=getattr(retrieval_model, "item_layout", None))
