"""The session lane: a sequence backbone served from per-user caches.

:class:`SessionTopK` is a :class:`~predictionio_tpu.ops.serving.
DeviceTopK` (the pattern is ``TwoStageTopK``: a subclass, its own
``BatchLane`` in the shared ``BatchDispatcher``, its rows in the AOT
ladder) whose store holds, beside the output table ``Y``:

- the backbone's weights (``ops/mla.py``, the ``glm_moe_dsa`` block);
- ``X``: every user's LAST hidden state (final norm applied), so that
  the inherited ``users`` lane answers a query without new events;
- a POOL of cache blocks: per layer a latent array ``[blocks, bs,
  lat_width]`` and an index-key array ``[blocks, bs, index_head_dim]``
  that share one block table, so a block id names a session's rows of
  both kinds in every layer. Block 0 is never handed out: padding
  writes land there.

A query ``(user, new events, k)`` appends the events to the user's
session and recommends: ONE dispatch runs the backbone over the
group's new tokens against the caches, writes their cache rows, scores
the output table, masks what the user has seen and takes the top-k,
fetched as one packed buffer. Queries of one user in one group are
applied in arrival order, each in a wave of its own, so every answer
reflects exactly its own prefix. A user without a session is prefilled
from the history the model stored (``prefill_chunk``, ``index_topk``
tokens a chunk), which is also how :meth:`warmup` builds the resident
sessions at deploy time. When the pool is full the session touched
longest ago gives up its blocks; its events stay on the host and its
next touch prefills it again.

Host-side bookkeeping (block tables, lengths, the events themselves)
lives under ``_sess_lock``; the device tables are swapped under
``_store_lock`` exactly as ``patch_users`` swaps its own.
"""

from __future__ import annotations

import collections
import logging
import threading
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from predictionio_tpu.ops.aot import lower_compile
from predictionio_tpu.ops.serving import (
    BatchLane,
    DeviceTopK,
    _bucket,
    _deliver,
    _Pending,
    _unpack,
)
from predictionio_tpu.utils import device_telemetry as _dtel
from predictionio_tpu.utils import metrics as _metrics
from predictionio_tpu.utils.tracing import span as _trace_span

logger = logging.getLogger(__name__)

SESS_EVENTS = 8          # new events one dispatch takes of a query
SESS_BATCHES = (1, 4, 8)  # query buckets of the lane's programs
SESS_MAX_BATCH = SESS_BATCHES[-1]   # queries one dispatch takes
SESS_BLOCK = 256         # cache rows a block of the pool holds
# The programs are laddered over the cached length in powers of two.
# The shortest bucket is this many selections (``index_topk``) long:
# under it the indexer and the selection cost a dispatch less than the
# weights it streams anyway, and every further bucket is four more
# programs to compile. The longest is the longest stored history's
# bucket doubled: the room a session has to grow before a redeploy.
SESS_FLOOR_SELECTIONS = 8
NO_ROW = -1              # the user row of a query row that writes none


class _Session:
    __slots__ = ("items", "length", "blocks", "touched")

    def __init__(self, items: np.ndarray):
        self.items = np.asarray(items, dtype=np.int32)
        self.length = 0          # events whose rows are in the cache
        self.blocks: List[int] = []
        self.touched = 0


def _dispatch_sess_group(srv: "SessionTopK",
                         group: List[_Pending]) -> None:
    """Session queries -> one dispatch a WAVE: the first step of every
    user in the group, then the second of those that have one, ... A
    step is a query's (at most ``SESS_EVENTS``) new events; a query
    with more is cut into steps of which only the last answers."""

    kmax = max(it.k for it in group)
    kb = srv._sess_kb(kmax)
    steps: Dict[int, List[Tuple[np.ndarray, Optional[int]]]] = {}
    for row, it in enumerate(group):
        uid, items = it.payload
        mine = steps.setdefault(int(uid), [])
        items = np.asarray(items, dtype=np.int32)
        cuts = list(range(0, max(len(items), 1), SESS_EVENTS))
        for j, a in enumerate(cuts):
            mine.append((items[a:a + SESS_EVENTS],
                         row if j == len(cuts) - 1 else None))
    idx = np.zeros((len(group), kb), dtype=np.int32)
    scores = np.full((len(group), kb), -np.inf, dtype=np.float32)
    wave = 0
    while True:
        rows = [(u, s[wave]) for u, s in steps.items() if len(s) > wave]
        if not rows:
            break
        for lo in range(0, len(rows), SESS_MAX_BATCH):
            part = rows[lo:lo + SESS_MAX_BATCH]
            wi, ws = srv.extend([(u, st[0]) for u, st in part], kb)
            for j, (_, st) in enumerate(part):
                if st[1] is not None:
                    idx[st[1]], scores[st[1]] = wi[j], ws[j]
        wave += 1
    _deliver(group, idx, scores)


class SessionTopK(DeviceTopK):
    """See the module docstring. ``item_factors``: the output table;
    ``theta``: the backbone's parameters as served
    (:func:`~predictionio_tpu.ops.mla.serving_theta` /
    ``draw_serving_theta``, without ``out_emb``); ``histories``: ``{user
    row: item ids, oldest first}``; ``pool_tokens``: the pool's cache
    rows (``SeqRecParams.session_pool_tokens``; 0: twice the stored
    histories); ``audit``: how many dispatches' audits the lane keeps
    (``SeqRecParams.session_audit``; 0: the programs compute none; see
    :meth:`audits`). A block holds ``SESS_BLOCK`` rows; the programs
    are laddered over ``SESS_BATCHES`` queries and over the cached
    length in powers of two from ``SESS_FLOOR_SELECTIONS x index_topk``
    to twice the longest stored history's bucket."""

    def __init__(self, item_factors, theta: Dict[str, Any], params,
                 n_users: int, histories: Optional[Dict[int, Any]] = None,
                 seen: Optional[Dict[int, np.ndarray]] = None,
                 pool_tokens: int = 0, audit: int = 0,
                 microbatch: Optional[bool] = None):
        import jax
        import jax.numpy as jnp

        from predictionio_tpu.ops import mla

        spec = mla.glm_spec(params)
        self._spec = spec
        n_users = int(n_users)
        X = np.zeros((n_users, spec.width), dtype=np.float32)
        if not seen:
            # what a user has seen is their history (an empty entry
            # keeps the bitmap when there is none)
            seen = {int(u): np.unique(np.asarray(h, dtype=np.int64))
                    for u, h in (histories or {}).items()}
            seen.setdefault(0, np.zeros(0, np.int64))
        super().__init__(X, item_factors, seen, n_users=n_users,
                         microbatch=microbatch)
        if self._mode == "int8" or self._shard is not None:
            raise ValueError("the session lane serves an fp32 or bf16 "
                             "store on one device")
        # the fused top-k kernel's item tile is sized for factor
        # stores, not for a model width of thousands: the head is a
        # plain product inside the session program
        self._kernel = "xla"
        self._theta = {k: v for k, v in theta.items() if k != "out_emb"}
        self._histories = {int(u): np.asarray(h, dtype=np.int32)
                           for u, h in (histories or {}).items()}
        self._bs = SESS_BLOCK
        lo = _bucket(SESS_FLOOR_SELECTIONS * spec.idx_topk, lo=self._bs)
        longest = max((len(h) for h in self._histories.values()), default=0)
        self._s_max = 2 * _bucket(longest, lo=lo)
        self._s_buckets = []
        s = lo
        while s <= self._s_max:
            self._s_buckets.append(s)
            s *= 2
        self._chunk = spec.idx_topk
        self._qb = min(32, self._chunk)
        stored = sum(len(h) for h in self._histories.values())
        tokens = int(pool_tokens) or max(2 * stored, 4 * lo)
        self._n_blocks = 1 + max(2, -(-tokens // self._bs))
        cache_dtype = jnp.dtype(spec.compute_dtype)
        with self._store_lock, _trace_span("store.upload"):
            shape = (self._n_blocks, self._bs)
            self._lat = tuple(jnp.zeros(shape + (spec.lat_width,),
                                        cache_dtype)
                              for _ in range(spec.n_layers))
            self._ik = tuple(jnp.zeros(shape + (spec.idx_dim,), cache_dtype)
                             for _ in range(spec.n_layers))
            jax.block_until_ready((self._lat, self._ik))
        self._sess_lock = threading.RLock()
        self._sessions: Dict[int, _Session] = {}
        self._free = list(range(self._n_blocks - 1, 0, -1))
        self._clock = 0
        self._audit_keep = int(audit)
        self._audits: collections.deque = collections.deque(
            maxlen=max(1, self._audit_keep))
        self._watched: Optional[set] = None
        self._token_rows = {"valid": 0, "padded": 0}
        self._sess_programs: Dict[Tuple, Any] = {}
        self._sess_batcher: Optional[BatchLane] = None
        if self._dispatcher is not None:
            self._sess_batcher = self._dispatcher.add_lane(
                "pio-microbatch-sess", max_batch=SESS_MAX_BATCH,
                dispatch_fn=_dispatch_sess_group)
        _metrics.SESS_CACHE_CAPACITY.set(
            (self._n_blocks - 1) * self._bs)
        _metrics.SESS_CACHE_TOKENS.set(0)

    # -- programs and the ladder ------------------------------------------

    @property
    def theta(self) -> Dict[str, Any]:
        """The backbone's device parameters plus the output table, as
        the reference reads them (``out_emb``: the store's ``Y``)."""
        with self._store_lock:
            return dict(self._theta, out_emb=self._Y)

    def _sess_kb(self, k: int) -> int:
        """The one k bucket the lane is laddered at (the largest the
        warm-up planned), or ``k``'s own beyond it."""
        return min(max(_bucket(k), _bucket(self._ladder_kmax)),
                   self.n_items)

    _ladder_kmax = 128
    _resident_s = 0.0     # what warmup() spent building the sessions

    def _extend_program(self, kb: int, S: int):
        key = ("sess", kb, S)
        prog = self._sess_programs.get(key)
        if prog is None:
            import jax

            from predictionio_tpu.ops import mla

            def sess_extend(theta, X, seen_bits, lat, ik, Y, ints):
                return mla.extend_step(
                    theta, X, seen_bits, lat, ik, Y, ints, spec=self._spec,
                    kb=kb, T=SESS_EVENTS, S=S, bs=self._bs,
                    n_items=self.n_items, mode=self._mode, mask_seen=True,
                    audit=bool(self._audit_keep))

            prog = jax.jit(sess_extend, donate_argnums=(1, 2, 3, 4))
            self._sess_programs[key] = prog
        return prog

    def _prefill_program(self, S: int):
        key = ("sesspre", S)
        prog = self._sess_programs.get(key)
        if prog is None:
            import jax

            from predictionio_tpu.ops import mla

            def sess_prefill(theta, X, lat, ik, ints):
                return mla.prefill_chunk(
                    theta, X, lat, ik, ints, spec=self._spec,
                    C=self._chunk, S=S, bs=self._bs, qb=self._qb)

            prog = jax.jit(sess_prefill, donate_argnums=(1, 2, 3))
            self._sess_programs[key] = prog
        return prog

    def _ints_width(self, T: int, S: int) -> int:
        return 3 + 2 * T + S // self._bs

    def _store_sig(self, tables: Dict[str, Any]) -> Tuple:
        return super()._store_sig(tables) + (
            tuple(self._lat[0].shape), str(self._lat[0].dtype),
            self._spec, bool(self._audit_keep))

    def aot_plan(self, max_k: int = 128,
                 batch_sizes: Tuple[int, ...] = ()) -> List[Tuple]:
        """The parent ladder plus ``("sess", kb, bb, S)`` for every
        (query bucket, cached-length bucket) at the largest k bucket,
        and ``("sesspre", chunk, S)`` for every cached-length bucket."""
        plan = super().aot_plan(max_k=max_k, batch_sizes=batch_sizes)
        self._ladder_kmax = max(e[1] for e in plan if e[0] == "user")
        kb = self._sess_kb(1)
        for bb in SESS_BATCHES:
            plan += [("sess", kb, bb, S) for S in self._s_buckets]
        plan += [("sesspre", self._chunk, S) for S in self._s_buckets]
        return plan

    def _aot_lower_entry(self, entry: Tuple, tables: Dict[str, Any]):
        import jax
        import jax.numpy as jnp

        i32 = jnp.int32
        with self._store_lock:
            lat, ik, theta = self._lat, self._ik, self._theta
        if entry[0] == "sess":
            _, kb, bb, S = entry
            return lower_compile(
                self._extend_program(kb, S), theta, tables["X"],
                tables["seen_bits"], lat, ik, tables["Y"],
                jax.ShapeDtypeStruct(
                    (bb, self._ints_width(SESS_EVENTS, S)), i32))
        if entry[0] == "sesspre":
            _, C, S = entry
            return lower_compile(
                self._prefill_program(S), theta, tables["X"], lat, ik,
                jax.ShapeDtypeStruct((self._ints_width(C, S),), i32))
        return super()._aot_lower_entry(entry, tables)

    def _warm_entry(self, entry: Tuple) -> None:
        if entry[0] not in ("sess", "sesspre"):
            return super()._warm_entry(entry)
        with self._sess_lock:
            if entry[0] == "sess":
                _, kb, bb, S = entry
                self._run_extend(np.zeros(
                    (bb, self._ints_width(SESS_EVENTS, S)), np.int32)
                    + self._pad_row(SESS_EVENTS, S), kb, S, n=0)
            else:
                _, C, S = entry
                ints = np.zeros(self._ints_width(C, S), np.int32)
                ints[0] = NO_ROW
                self._run_prefill(ints, S, n=0)

    def warmup(self, max_k: int = 128,
               batch_sizes: Tuple[int, ...] = ()) -> Dict[str, int]:
        """The ladder, then the resident sessions: every stored
        history is prefilled (most events first) until the pool is
        full; the rest are prefilled at their first touch."""
        import time

        t_start = time.perf_counter()
        stats = super().warmup(max_k=max_k, batch_sizes=batch_sizes)
        t0 = time.perf_counter()
        with _trace_span("sess.resident",
                         attributes={"sessions": len(self._histories)}):
            order = sorted(self._histories,
                           key=lambda u: -len(self._histories[u]))
            for u in order:
                with self._sess_lock:
                    need = -(-len(self._histories[u]) // self._bs) + 1
                    if u in self._sessions or need > len(self._free):
                        continue
                    self._ensure_session(u, busy=())
        self._resident_s = time.perf_counter() - t0
        logger.info("session lane: ladder %s in %.1fs, %d resident "
                    "sessions (%d events) prefilled in %.1fs", stats,
                    t0 - t_start, len(self._sessions),
                    sum(s.length for s in self._sessions.values()),
                    self._resident_s)
        return stats

    # -- the cache manager -------------------------------------------------

    def _pad_row(self, T: int, S: int) -> np.ndarray:
        """A padded query row: no user row, nothing cached, nothing
        new, block 0 everywhere."""
        row = np.zeros(self._ints_width(T, S), np.int32)
        row[0] = NO_ROW
        return row

    def _phys(self, sess: _Session, pos: np.ndarray) -> np.ndarray:
        blocks = np.asarray(sess.blocks, dtype=np.int64)
        return (blocks[pos // self._bs] * self._bs
                + pos % self._bs).astype(np.int32)

    def _reserve(self, sess: _Session, length: int, busy) -> None:
        """Blocks for ``length`` cached events, evicting the sessions
        touched longest ago (never one of ``busy``) when none is
        free."""
        need = -(-int(length) // self._bs) - len(sess.blocks)
        if need <= 0:
            return
        with _trace_span("sess.cache_alloc", attributes={"blocks": need}):
            while len(self._free) < need:
                victims = [(s.touched, u) for u, s in
                           self._sessions.items()
                           if s is not sess and u not in busy and s.blocks]
                if not victims:
                    raise RuntimeError(
                        f"the session pool ({self._n_blocks - 1} blocks "
                        f"of {self._bs}) cannot hold {length} events "
                        "beside the sessions of this dispatch")
                self.release(min(victims)[1])
                _metrics.SESS_EVICTIONS.inc()
            sess.blocks += [self._free.pop() for _ in range(need)]
            _metrics.SESS_CACHE_TOKENS.set(
                (self._n_blocks - 1 - len(self._free)) * self._bs)

    def release(self, uid: int) -> None:
        """Give a session's blocks back; its events stay on the host
        (the next touch prefills them again)."""
        with self._sess_lock:
            sess = self._sessions.pop(int(uid), None)
            if sess is None:
                return
            self._histories[int(uid)] = sess.items[:sess.length]
            self._free += sess.blocks
            _metrics.SESS_CACHE_TOKENS.set(
                (self._n_blocks - 1 - len(self._free)) * self._bs)

    def open_session(self, uid: int, items) -> None:
        """(Re)build ``uid``'s session from ``items`` (oldest first)."""
        with self._sess_lock:
            self.release(uid)
            self._histories[int(uid)] = np.asarray(items, dtype=np.int32)
            self._ensure_session(int(uid), busy=())

    def _s_bucket(self, length: int) -> int:
        for s in self._s_buckets:
            if length <= s:
                return s
        raise ValueError(
            f"a session of {length} events is past the lane's longest "
            f"({self._s_max}: twice the longest stored history's bucket)")

    def _table(self, sess: _Session, S: int) -> np.ndarray:
        t = np.zeros(S // self._bs, np.int32)
        n = min(len(sess.blocks), len(t))
        t[:n] = sess.blocks[:n]
        return t

    def _ensure_session(self, uid: int, busy) -> _Session:
        """The user's session, prefilled from the stored history when
        it has none. Caller holds ``_sess_lock``."""
        sess = self._sessions.get(uid)
        if sess is not None:
            return sess
        hist = self._histories.get(uid, np.zeros(0, np.int32))
        self._s_bucket(len(hist))
        sess = _Session(hist)
        self._sessions[uid] = sess
        self._prefill(sess, uid, busy)
        return sess

    def _prefill(self, sess: _Session, row: int, busy):
        """``sess.items`` through the prefill program into blocks of
        the pool, a chunk at a time; the last chunk leaves the
        history's last hidden state in user row ``row`` (``NO_ROW``:
        in none). Returns that state (device), None for no events."""
        hist, C, h_last = sess.items, self._chunk, None
        if len(hist) == 0:
            return None
        with _trace_span("sess.prefill", attributes={"events": len(hist)}):
            self._reserve(sess, len(hist), busy)
            for p0 in range(0, len(hist), C):
                n = min(C, len(hist) - p0)
                S = self._s_bucket(p0 + C)
                ints = np.zeros(self._ints_width(C, S), np.int32)
                final = p0 + n == len(hist)
                ints[0] = row if final else NO_ROW
                ints[1], ints[2] = p0, n
                ints[3:3 + n] = hist[p0:p0 + n]
                ints[3 + C:3 + C + n] = self._phys(sess,
                                                   np.arange(p0, p0 + n))
                ints[3 + 2 * C:] = self._table(sess, S)
                h_last = self._run_prefill(ints, S, n=n)
            sess.length = len(hist)
        return h_last

    def encode(self, items) -> np.ndarray:
        """The last hidden state (final norm applied, float32) of a
        history that is NOBODY's session yet: prefilled into blocks
        that are given back at once, and written to no user row. What
        the fold-in writes for a user the store has no row for."""
        items = np.asarray(items, dtype=np.int32)
        self._s_bucket(len(items))
        with self._sess_lock:
            sess = _Session(items)
            try:
                h = self._prefill(sess, NO_ROW, busy=())
            finally:
                self._free += sess.blocks
                _metrics.SESS_CACHE_TOKENS.set(
                    (self._n_blocks - 1 - len(self._free)) * self._bs)
        return np.zeros(self._spec.width, np.float32) if h is None \
            else np.asarray(h, dtype=np.float32)

    def _run_prefill(self, ints: np.ndarray, S: int, n: int):
        def take(out):
            self._X, self._lat, self._ik, h = out
            return h

        out = self._dispatch_entry(
            ("sesspre", self._chunk, S),
            lambda: self._prefill_program(S),
            lambda: (self._theta, self._X, self._lat, self._ik, ints),
            batch=n, bucket=self._chunk, take=take)
        if n:
            _metrics.SESS_TOKENS.inc(amount=n, program="prefill")
        return out

    # -- serving -----------------------------------------------------------

    def _run_extend(self, ints: np.ndarray, kb: int, S: int, n: int):
        """One extend dispatch; returns the fetched packed buffer and
        the (device) audit outputs (None from a lane built without
        ``audit``)."""
        got = {}

        def take(out):
            packed, self._X, self._seen_bits, self._lat, self._ik, \
                got["audit"] = out
            return packed

        bb = ints.shape[0]
        out = self._dispatch_entry(
            ("sess", kb, bb, S), lambda: self._extend_program(kb, S),
            lambda: (self._theta, self._X, self._seen_bits, self._lat,
                     self._ik, self._Y, ints),
            batch=n, bucket=bb, take=take)
        with _dtel.stage("fetchUs", "dispatch.fetch", done=True):
            host = np.asarray(out)
        return host, got["audit"]

    def extend(self, rows: List[Tuple[int, np.ndarray]],
               kb: int) -> Tuple[np.ndarray, np.ndarray]:
        """Append each ``(user row, new events)`` (distinct users, at
        most ``SESS_EVENTS`` events each) and return ``(item ids [n,
        kb], scores [n, kb])``: one dispatch."""
        n = len(rows)
        T = SESS_EVENTS
        with self._sess_lock, _trace_span(
                "sess.extend", attributes={"queries": n}):
            with _dtel.stage("formUs", "batch.form"):
                busy = {int(u) for u, _ in rows}
                if len(busy) != n:
                    raise ValueError("one dispatch takes one query a user")
                sessions = []
                for u, items in rows:
                    sess = self._ensure_session(int(u), busy)
                    self._reserve(sess, sess.length + len(items), busy)
                    sessions.append(sess)
                S = self._s_bucket(max(s.length + len(it) for s, (_, it)
                                       in zip(sessions, rows)))
                bb = next(b for b in SESS_BATCHES if b >= n)
                ints = np.tile(self._pad_row(T, S), (bb, 1))
                for j, (sess, (u, items)) in enumerate(
                        zip(sessions, rows)):
                    m = len(items)
                    ints[j, 0], ints[j, 1], ints[j, 2] = u, sess.length, m
                    ints[j, 3:3 + m] = items
                    ints[j, 3 + T:3 + T + m] = self._phys(
                        sess, np.arange(sess.length, sess.length + m))
                    ints[j, 3 + 2 * T:] = self._table(sess, S)
            host, audit = self._run_extend(ints, kb, S, n)
            self._clock += 1
            tokens = 0
            for j, (sess, (u, items)) in enumerate(zip(sessions, rows)):
                m = len(items)
                if m:
                    if sess.length + m > len(sess.items):
                        grown = np.zeros(max(2 * len(sess.items),
                                             sess.length + m, 64), np.int32)
                        grown[:sess.length] = sess.items[:sess.length]
                        sess.items = grown
                    sess.items[sess.length:sess.length + m] = items
                    sess.length += m
                    tokens += m
                sess.touched = self._clock
            if audit is not None and (self._watched is None or any(
                    int(u) in self._watched for u, _ in rows)):
                self._audits.append((audit, bb, [
                    (int(u), sess.length) for sess, (u, _)
                    in zip(sessions, rows)]))
            if tokens:
                _metrics.SESS_TOKENS.inc(amount=tokens, program="extend")
            for kind, rows_ in (("valid", tokens),
                                ("padded", bb * T - tokens)):
                _metrics.SESS_TOKEN_ROWS.inc(amount=rows_, kind=kind)
                self._token_rows[kind] += rows_
            _metrics.SESS_POSITIONS.inc(
                amount=sum(s_.length for s_ in sessions))
        idx, scores = _unpack(host[:, :2 * kb], kb)
        selected, eligible, local, touched = (
            float(c) for c in host[0, 2 * kb:].view(np.float32))
        if eligible > 0:
            _metrics.SESS_SELECTED_SHARE.set(selected / eligible)
            _metrics.SESS_SELECTED.inc(amount=selected, kind="selected")
            _metrics.SESS_SELECTED.inc(amount=eligible, kind="eligible")
        if local > 0:
            _metrics.SESS_LOCAL_PICKS.inc(amount=local)
        if touched > 0:
            _metrics.SESS_EXPERTS_TOUCHED.inc(amount=touched)
        return idx[:n], scores[:n]

    def sess_topk(self, uid: int, items, k: int
                  ) -> Tuple[np.ndarray, np.ndarray]:
        """Append ``items`` (item rows, oldest first; may be empty) to
        ``uid``'s session and return its top ``k`` ``(item rows,
        scores)``, seen items masked. Concurrent callers share
        dispatches through the ``pio-microbatch-sess`` lane."""
        items = np.asarray(items, dtype=np.int32).reshape(-1)
        with _trace_span("device.sess_topk",
                         attributes={"k": int(k), "events": len(items)}) \
                as sp:
            if self._sess_batcher is not None:
                return self._sess_batcher.submit((int(uid), items), int(k),
                                                 span=sp)
            group = [_Pending((int(uid), items), int(k), 0.0, 0, 0.0)]
            group[0].future.set_running_or_notify_cancel()
            _dispatch_sess_group(self, group)
            res, row = group[0].future.result()
            return res.render(row, int(k))

    # -- what the lane knows of a session ----------------------------------

    def session_events(self, uid: int) -> np.ndarray:
        """The events cached for ``uid`` (a copy), oldest first."""
        with self._sess_lock:
            sess = self._sessions.get(int(uid))
            if sess is None:
                return np.array(self._histories.get(
                    int(uid), np.zeros(0, np.int32)))
            return np.array(sess.items[:sess.length])

    def last_hidden(self, uid: int) -> np.ndarray:
        """``uid``'s last hidden state (final norm applied), float32:
        its row of the store's user table."""
        with self._store_lock:
            row = self._X[int(uid)]
        return np.asarray(row, dtype=np.float32)

    def watch(self, uids=None) -> None:
        """Keep the audits of dispatches that answer one of ``uids``
        only (None: of every dispatch), and drop those kept so far."""
        with self._sess_lock:
            self._watched = None if uids is None else {int(u) for u in uids}
            self._audits.clear()

    def audits(self, uid: int) -> List[Dict[str, Any]]:
        """What the lane computed for ``uid`` in the dispatches whose
        audit it still keeps (the latest ``audit`` of them; oldest
        first), fetched from the device. An answer: ``length`` (the
        events it reflects), ``scores`` (every item's, before the seen
        mask) and, for the query's last event, ``layers`` (the residual
        stream after every layer), ``selected`` (the positions a layer
        attended over), ``lat`` / ``ik`` (the two cache rows a layer
        wrote), ``picks`` / ``gates`` / ``h2`` (an expert layer's
        router picks, their weights, and the router's input);
        ``slot``, ``queries`` and ``bucket`` say where in which
        dispatch it rode. Empty when the lane was built without
        ``audit``."""
        with self._sess_lock:
            kept = list(self._audits)
        import jax

        out = []
        for audit, bb, rows in kept:
            for slot, (u, length) in enumerate(rows):
                if u != int(uid):
                    continue
                host = jax.device_get(audit)
                got = {k: v[:, slot] for k, v in host.items()
                       if k != "scores"}
                got.update(scores=host["scores"][slot][:self.n_items],
                           length=int(length), slot=slot,
                           queries=len(rows), bucket=int(bb))
                out.append(got)
        return out

    def close(self) -> None:
        """Release the dispatcher AND the pool's device memory, and
        unload the lane's programs (they hold their scratch): the lane
        serves nothing afterwards; the weights stay readable."""
        super().close()
        with self._sess_lock, self._store_lock:
            for a in self._lat + self._ik:
                if not a.is_deleted():
                    a.delete()
            self._lat = self._ik = ()
            self._sessions.clear()
            self._audits.clear()
            self._aot_programs.discard(lambda key: True)
            self._sess_programs.clear()

    def stats(self) -> Dict[str, Dict[str, int]]:
        out = super().stats()
        if self._sess_batcher is not None:
            out["sess"] = self._sess_batcher.stats()
        return out

    def session_report(self) -> Dict[str, Any]:
        with self._sess_lock:
            held = self._n_blocks - 1 - len(self._free)
            rows = dict(self._token_rows)
            return {"sessions": len(self._sessions),
                    "tokenRows": rows,
                    # of the token rows dispatched, the share the
                    # attend loop never runs
                    "skippedRowShare": rows["padded"]
                    / max(sum(rows.values()), 1),
                    "blockTokens": self._bs,
                    "cacheTokens": held * self._bs,
                    "capacityTokens": (self._n_blocks - 1) * self._bs,
                    "events": int(sum(s.length for s in
                                      self._sessions.values())),
                    "lengthBuckets": list(self._s_buckets),
                    "residentSeconds": self._resident_s}

    def memory_report(self) -> Dict[str, Any]:
        report = super().memory_report()
        with self._store_lock:
            theta, lat, ik = self._theta, self._lat, self._ik
        extra = {
            "backbone": {"bytes": int(sum(v.nbytes for v in
                                          theta.values())),
                         "scaleBytes": 0,
                         "dtype": self._spec.compute_dtype},
            "sessionLatents": {"bytes": int(sum(a.nbytes for a in lat)),
                               "scaleBytes": 0,
                               "dtype": self._spec.compute_dtype},
            "sessionIndexKeys": {"bytes": int(sum(a.nbytes for a in ik)),
                                 "scaleBytes": 0,
                                 "dtype": self._spec.compute_dtype}}
        report["components"].update(extra)
        report["totalBytes"] += sum(c["bytes"] for c in extra.values())
        report["sessions"] = self.session_report()
        return report
