"""Slates generated by block diffusion, served from per-user caches:
the ``sdar_moe`` block (``ops/sdar.py``) as a backbone of the session
lane (``ops/sessions.py::SessionTopK``).

A query ``(user, new events, num)`` asks for a SLATE of ``num`` items:
its events are appended to the user's session (those that fill whole
blocks are committed to the cache in one prefill-shaped pass under the
block-causal mask, :func:`sdar.commit_events`; the rest stay the
session's tail, ids only), then the slate is decoded a block a ROUND
(:func:`sdar.slate_round`: the denoising passes as a device loop, then
the commit pass). A slate of 8-32 items is 2-9 rounds, so the lane's
dispatch function does ONE round for its group and hands the queries
it has not finished back to the dispatcher
(``BatchDispatcher._carry``): the next group is those queries and then
new arrivals, other lanes are served between two rounds, and a query
is delivered when ITS last block is done.

Two kinds of state beside the manager's:

- a query's SCRATCH blocks: pool blocks (one, at 256 rows a block)
  that hold the cache rows of the query's own decoded blocks (its
  tail's rows too: they belong to the first decoded block), read by
  its later blocks, given back at its end, never part of the user's
  history;
- the query's view of its session (cached length, the tail's ids),
  taken when its events are in. ONE query of a user is in flight at a
  time: a second one waits unopened (handed back like an unfinished
  one), so every slate reflects exactly its own prefix and the seen
  bitmap it is masked with holds exactly the events before it. A
  session with a query in flight is not evicted.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from predictionio_tpu.ops.aot import lower_compile
from predictionio_tpu.ops import sdar
from predictionio_tpu.ops.sdar import COUNTERS, NEW_EVENTS
from predictionio_tpu.ops.serving import _bucket, _deliver, _Pending
from predictionio_tpu.ops.sessions import NO_ROW, one_kind, waves
from predictionio_tpu.utils import device_telemetry as _dtel
from predictionio_tpu.utils import metrics as _metrics
from predictionio_tpu.utils.tracing import span as _trace_span

# query rows of a round dispatch: ONE bucket. A padded row reads no
# cache and routes to no expert (``sdar.moe_layer``), so it costs the
# 32-row products it rides in and nothing else, and a round of one
# query streams the same attention weights and head as a round of
# eight: a narrower program would save nothing a round and cost a
# compile a cached-length bucket.
SLATE_BATCHES = (8,)
SLATE_MAX_BATCH = SLATE_BATCHES[-1]
SLATE_MAX = 32          # items the longest slate holds
EVENT_ROWS = 8          # tokens of one query a commit_events dispatch takes
SLATE_CHUNK = 2048      # tokens a prefill chunk holds


class _Slate:
    """A query between two of its rounds (its ``_Pending.payload``)."""

    __slots__ = ("uid", "num", "sess", "len0", "tail", "pos0", "scratch",
                 "slate", "conf", "rounds")

    def __init__(self, uid: int, num: int, sess, scratch: List[int]):
        self.uid, self.num, self.sess = uid, num, sess
        # the session as this query found it, set when its events are
        # in: cached length, the tail's ids, the next block's position
        self.len0 = self.pos0 = 0
        self.tail = None
        self.scratch = scratch
        self.slate: List[int] = []
        self.conf: List[float] = []
        self.rounds = 0

    @property
    def done(self) -> bool:
        return len(self.slate) >= self.num


class SdarBackbone:
    """See the module docstring; the manager's side of the interface
    is ``sessions.GlmBackbone``'s."""

    entries = ("sess", "sesspre", "sessev")
    max_batch = SLATE_MAX_BATCH
    max_positions = 0

    def __init__(self, params):
        self.spec = spec = sdar.sdar_spec(params)
        self.width = spec.width
        self.compute_dtype = spec.compute_dtype
        self.kinds = one_kind(spec.n_layers)
        self.commit_multiple = B = spec.block_len
        if B - 1 > NEW_EVENTS or EVENT_ROWS % B:
            raise ValueError(f"block_length {B}: a divisor of {EVENT_ROWS}")
        self.cache_rows = (("k", spec.kv_width, "sessionKeys"),
                           ("v", spec.kv_width, "sessionValues"))
        # a prefill chunk: whole blocks, a quarter of the longest
        # session's bucket at most; the shortest cached-length bucket
        # is four chunks
        self.chunk = max(4 * B, min(
            SLATE_CHUNK, _bucket(max(int(params.max_seq_len), 16)) // 4))
        self.chunk -= self.chunk % B
        self.floor = 4 * self.chunk
        self.qb = min(32, self.chunk)
        while self.chunk % self.qb:
            self.qb -= 1
        self._scratch_blocks = 0    # pool blocks queries in flight hold
        if SLATE_MAX % B:
            # (so that the rows of a slate's blocks but the last, its
            # tail's too, are at most SLATE_MAX: the scratch rows a
            # round reads)
            raise ValueError(f"block_length {B}: a divisor of {SLATE_MAX}")

    def serving_theta(self, theta):
        return sdar.serving_theta(theta, self.spec)

    def draw_theta(self, V: int, params):
        return sdar.draw_serving_theta(V, params)

    # -- programs and their ladder entries -------------------------------------

    def prefill_program(self, m, S: int):
        def make():
            import jax

            def sess_prefill(theta, X, pool, ints):
                return sdar.prefill_chunk(
                    theta, X, pool, ints, spec=self.spec, C=self.chunk,
                    S=S, bs=m._bs, qb=self.qb)

            return jax.jit(sess_prefill, donate_argnums=(1, 2))

        return m._program(("sesspre", S), make)

    def round_program(self, m, S: int):
        def make():
            import jax

            def slate_round(theta, seen_bits, pool, Y, ints):
                return sdar.slate_round(
                    theta, seen_bits, pool, Y, ints, spec=self.spec, S=S,
                    J=SLATE_MAX, bs=m._bs, n_items=m.n_items,
                    mode=m._mode, audit=bool(m._audit_keep))

            return jax.jit(slate_round, donate_argnums=(1, 2))

        return m._program(("sess", S), make)

    def events_program(self, m, S: int):
        def make():
            import jax

            def slate_commit_events(theta, seen_bits, pool, ints):
                return sdar.commit_events(
                    theta, seen_bits, pool, ints, spec=self.spec,
                    T=EVENT_ROWS, S=S, bs=m._bs,
                    audit=bool(m._audit_keep))

            return jax.jit(slate_commit_events, donate_argnums=(1, 2))

        return m._program(("sessev", S), make)

    def _round_width(self, m, S: int) -> int:
        return sdar.round_width(self.spec.block_len, SLATE_MAX, S, m._bs)

    def plan(self, m, kb: int) -> List[Tuple]:
        """``("sess", block, bb, S)`` (a round) and ``("sessev", rows,
        bb, S)`` (new events committed) for every (query bucket,
        cached-length bucket)."""
        return [(kind, rows, bb, S)
                for kind, rows in (("sess", self.spec.block_len),
                                   ("sessev", EVENT_ROWS))
                for bb in SLATE_BATCHES for S in m._s_buckets]

    def lower(self, m, entry: Tuple, tables, theta, pool):
        import jax
        import jax.numpy as jnp

        kind, _, bb, S = entry
        if kind == "sessev":
            return lower_compile(
                self.events_program(m, S), theta, tables["seen_bits"], pool,
                jax.ShapeDtypeStruct(
                    (bb, sdar.events_width(EVENT_ROWS, S, m._bs)),
                    jnp.int32))
        return lower_compile(
            self.round_program(m, S), theta, tables["seen_bits"], pool,
            tables["Y"], jax.ShapeDtypeStruct(
                (bb, self._round_width(m, S)), jnp.int32))

    def warm(self, m, entry: Tuple) -> None:
        kind, _, bb, S = entry
        if kind == "sessev":
            ints = np.zeros((bb, sdar.events_width(EVENT_ROWS, S, m._bs)),
                            np.int32)
            ints[:, 0], ints[:, 3] = NO_ROW, -1
            self._run_events(m, ints, S, n=0)
        else:
            self._run_round(m, self._pad_rows(m, bb, S), S, n=0)

    def _pad_rows(self, m, bb: int, S: int) -> np.ndarray:
        """Padded round rows: no user, no rows, nothing cached, block 0
        everywhere, no audit."""
        ints = np.zeros((bb, self._round_width(m, S)), np.int32)
        ints[:, 0], ints[:, 7] = NO_ROW, -1
        R = self.spec.block_len
        # the slate so far, the new events: none
        ints[:, 8 + R:8 + R + SLATE_MAX + NEW_EVENTS] = -1
        return ints

    def _scratch_rows(self, m, st: "_Slate", lo: int, hi: int) -> np.ndarray:
        """Pool rows of the query's scratch positions ``lo .. hi``
        (counted from its cached length)."""
        at = np.arange(lo, hi)
        return (np.asarray(st.scratch)[at // m._bs] * m._bs
                + at % m._bs).astype(np.int32)

    def _run_round(self, m, ints: np.ndarray, S: int, n: int):
        got = {}

        def take(out):
            packed, m._seen_bits, m._pool, got["audit"] = out
            return packed

        bb = ints.shape[0]
        out = m._dispatch_entry(
            ("sess", self.spec.block_len, bb, S),
            lambda: self.round_program(m, S),
            lambda: (m._theta, m._seen_bits, m._pool, m._Y, ints),
            batch=n, bucket=bb, take=take)
        with _dtel.stage("fetchUs", "dispatch.fetch", done=True):
            host = np.asarray(out)
        return host, got["audit"]

    def _run_events(self, m, ints: np.ndarray, S: int, n: int):
        got = {}

        def take(out):
            m._seen_bits, m._pool, got["audit"] = out
            return m._seen_bits

        bb = ints.shape[0]
        m._dispatch_entry(
            ("sessev", EVENT_ROWS, bb, S), lambda: self.events_program(m, S),
            lambda: (m._theta, m._seen_bits, m._pool, ints),
            batch=n, bucket=bb, take=take)
        return got["audit"]

    # -- a query's first touch: events in, whole blocks committed ---------------

    def _open(self, m, fresh: List[_Pending]) -> None:
        """The new arrivals of a group (distinct users, none with a
        query in flight): each one's events behind its session, the
        tokens that fill whole blocks committed (a dispatch a wave: at
        most ``EVENT_ROWS`` tokens a query each), then its
        :class:`_Slate` with a scratch block. A query that cannot be
        opened fails alone."""
        B = self.spec.block_len
        busy = {int(it.payload[0]) for it in fresh}
        steps: Dict[int, list] = {}
        for it in fresh:
            uid, items = it.payload
            uid = int(uid)
            try:
                if not 0 < it.k <= SLATE_MAX:
                    raise ValueError(
                        f"a slate holds 1 to {SLATE_MAX} items, not {it.k}")
                # (outside every stage: a user without a session is
                # prefilled here, in dispatches with records of their
                # own)
                sess = m._ensure_session(uid, busy)
            except Exception as e:  # noqa: BLE001 — this query alone
                it.future.set_exception(e)
                continue
            with _dtel.stage("bookUs", "batch.book"):
                try:
                    m._s_bucket(sess.events + len(items))
                    # scratch: the rows of the slate's blocks, its
                    # tail's too
                    need = -(-(self.spec.block_len - 1 + it.k) // m._bs)
                    m._make_room(need, sess, busy)
                except Exception as e:  # noqa: BLE001 — this query alone
                    it.future.set_exception(e)
                    continue
                sess.append(np.asarray(items, dtype=np.int32))
                whole = (sess.events - sess.length) // B * B
                steps[uid] = [(sess.length + a,
                               min(EVENT_ROWS, whole - a))
                              for a in range(0, whole, EVENT_ROWS)]
                sess.inflight += 1
                self._scratch_blocks += need
                it.payload = _Slate(uid, int(it.k), sess,
                                    [m._free.pop() for _ in range(need)])
        for rows in waves(steps):
            for lo in range(0, len(rows), SLATE_MAX_BATCH):
                self._commit(m, rows[lo:lo + SLATE_MAX_BATCH], busy)
        with _dtel.stage("bookUs", "batch.book"):
            for it in fresh:
                st = it.payload
                if isinstance(st, _Slate):
                    st.len0 = st.pos0 = int(st.sess.length)
                    st.tail = np.array(
                        st.sess.items[st.len0:st.sess.events])
            self._note_state(m)
            m._note_fill()

    def _commit(self, m, rows, busy) -> None:
        """One ``commit_events`` dispatch: ``rows``: ``(user, (first
        position, tokens))`` of distinct users."""
        T = EVENT_ROWS
        with _trace_span("sess.slate.commit_events", attributes={
                "queries": len(rows),
                "tokens": int(sum(st[1] for _, st in rows))}):
            with _dtel.stage("formUs", "batch.form"):
                sessions = [m._sessions[u] for u, _ in rows]
                for sess, (_, (p0, n)) in zip(sessions, rows):
                    m._reserve(sess, p0 + n, busy)
                S = m._s_bucket(max(p0 + n for _, (p0, n) in rows))
                bb = next(b for b in SLATE_BATCHES if b >= len(rows))
                ints = np.zeros((bb, sdar.events_width(T, S, m._bs)),
                                np.int32)
                ints[:, 0], ints[:, 3] = NO_ROW, -1
                watched = [j for j, (u, _) in enumerate(rows)
                           if m._audit_keep and (m._watched is None
                                                 or u in m._watched)]
                if watched:
                    ints[0, 3] = watched[0]
                for j, (sess, (u, (p0, n))) in enumerate(
                        zip(sessions, rows)):
                    ints[j, 0], ints[j, 1], ints[j, 2] = u, p0, n
                    ints[j, 4:4 + n] = sess.items[p0:p0 + n]
                    ints[j, 4 + T:4 + T + n] = m._phys(
                        sess, np.arange(p0, p0 + n))
                    ints[j, 4 + 2 * T:] = m._table(sess, S)
            audit = self._run_events(m, ints, S, len(rows))
            with _dtel.stage("bookUs", "batch.book", done=True):
                tokens = 0
                for sess, (_, (p0, n)) in zip(sessions, rows):
                    sess.length = p0 + n
                    tokens += n
                _metrics.SESS_TOKENS.inc(amount=tokens, program="extend")
                if watched and audit is not None:
                    u, (p0, n) = rows[watched[0]]
                    m._audits.append((audit, {
                        "kind": "events", "uid": int(u), "len0": int(p0),
                        "tokens": int(n), "ids": np.array(
                            sessions[watched[0]].items[p0:p0 + n])}))

    def _note_state(self, m) -> None:
        live = list(m._sessions.values())
        _metrics.SLATE_SCRATCH_BLOCKS.set(self._scratch_blocks)
        _metrics.SESS_TAIL_TOKENS.set(sum(s.events - s.length
                                          for s in live))

    # -- a round -------------------------------------------------------------------

    def _round(self, m, group: List[_Pending]) -> None:
        """One block of every query in ``group`` (distinct users):
        one dispatch."""
        spec = self.spec
        R, J = spec.block_len, SLATE_MAX
        slates: List[_Slate] = [it.payload for it in group]
        n = len(slates)
        carried = sum(st.rounds > 0 for st in slates)
        with _trace_span("sess.slate.round", attributes={
                "rows": n, "carried": carried}) as sp:
            with _dtel.stage("formUs", "batch.form"):
                S = m._s_bucket(max(st.len0 for st in slates))
                bb = next(b for b in SLATE_BATCHES if b >= n)
                ints = self._pad_rows(m, bb, S)
                watched = [j for j, st in enumerate(slates)
                           if m._audit_keep and (m._watched is None
                                                 or st.uid in m._watched)]
                if watched:
                    ints[0, 7] = watched[0]
                shape = []
                for j, st in enumerate(slates):
                    fixed = st.tail if st.rounds == 0 else st.tail[:0]
                    rows = min(R, len(fixed) + st.num - len(st.slate))
                    shape.append((len(fixed), rows))
                    held = st.pos0 - st.len0
                    ints[j, :6] = (st.uid, st.len0, st.pos0, rows,
                                   len(fixed), held)
                    ints[j, 8:8 + len(fixed)] = fixed
                    ints[j, 8 + R:8 + R + len(st.slate)] = st.slate
                    c = 8 + R + J
                    ints[j, c:c + len(st.tail)] = st.tail
                    c += NEW_EVENTS
                    ints[j, c:c + held] = self._scratch_rows(m, st, 0, held)
                    c += J
                    ints[j, c:c + rows] = self._scratch_rows(
                        m, st, held, held + rows)
                    ints[j, c + R:] = m._table(st.sess, S)
            host, audit = self._run_round(m, ints, S, n)
            with _dtel.stage("bookUs", "batch.book", done=True):
                m._clock += 1
                conf = host[:, R:2 * R].view(np.float32)
                when = host[:, 2 * R:3 * R]
                device_passes, touched, read = (
                    int(c) for c in host[0, 3 * R + 1:3 * R + 1 + COUNTERS])
                unmasked = row_passes = 0
                for j, (st, (n_fixed, rows)) in enumerate(zip(slates, shape)):
                    st.slate += host[j, n_fixed:rows].tolist()
                    st.conf += conf[j, n_fixed:rows].tolist()
                    st.pos0 += rows
                    st.rounds += 1
                    st.sess.touched = m._clock
                    unmasked += rows - n_fixed
                    row_passes += int(host[j, 3 * R])
                if watched and audit is not None:
                    st = slates[watched[0]]
                    n_fixed, rows = shape[watched[0]]
                    m._audits.append((audit, {
                        "kind": "round", "uid": st.uid, "len0": st.len0,
                        "pos0": st.pos0 - rows, "rows": rows,
                        "fixed": np.array(st.tail[:n_fixed]),
                        "tail": np.array(st.tail), "num": st.num,
                        "taken": list(st.slate[:len(st.slate)
                                               - (rows - n_fixed)]),
                        "round": st.rounds - 1, "slot": watched[0],
                        "queries": n, "bucket": bb,
                        "tokens": host[watched[0], :R].copy(),
                        "conf": conf[watched[0]].copy(),
                        "when": when[watched[0]].copy()}))
                _metrics.SLATE_ROUNDS.inc(amount=n)
                _metrics.SLATE_PASSES.inc(amount=row_passes, kind="query")
                _metrics.SLATE_PASSES.inc(amount=device_passes, kind="device")
                _metrics.SLATE_TOKENS_UNMASKED.inc(amount=unmasked)
                _metrics.SLATE_EXPERTS_TOUCHED.inc(amount=touched)
                _metrics.SLATE_CACHE_ROWS_READ.inc(amount=read)
                if carried:
                    _metrics.SLATE_CARRIED.inc(amount=carried)
                fields = {"passes": device_passes, "tokensUnmasked": unmasked,
                          "carried": carried, "lengthBucket": S}
                if sp is not None:      # (no trace is being recorded)
                    sp.attributes.update(fields)
                _dtel.note_fields(**fields)

    def _finish(self, m, st: _Slate) -> None:
        m._free += st.scratch
        self._scratch_blocks -= len(st.scratch)
        st.scratch = []
        st.sess.inflight -= 1
        self._note_state(m)
        m._note_fill()

    def dispatch(self, m, group: List[_Pending]
                 ) -> Optional[List[_Pending]]:
        """The lane's dispatch function: open the group's new arrivals
        (one query of a user is in flight at a time: a second one waits
        unopened, so that every slate reflects exactly its own prefix),
        decode ONE block of every open query in one dispatch, deliver
        the queries whose slate is whole and hand the others back."""
        with m._sess_lock:
            try:
                with _dtel.stage("bookUs", "batch.book"):
                    flying = {it.payload.uid for it in group
                              if isinstance(it.payload, _Slate)}
                    fresh = []
                    for it in group:
                        if isinstance(it.payload, _Slate):
                            continue
                        uid = int(it.payload[0])
                        sess = m._sessions.get(uid)
                        if uid not in flying and not (sess
                                                      and sess.inflight):
                            flying.add(uid)
                            fresh.append(it)
                if fresh:
                    self._open(m, fresh)
                now = [it for it in group
                       if isinstance(it.payload, _Slate)]
                if now:
                    self._round(m, now)
            except BaseException:
                # the dispatcher fails every query of the group
                for it in group:
                    if isinstance(it.payload, _Slate):
                        self._finish(m, it.payload)
                        it.payload = (it.payload.uid, ())
                raise
            with _dtel.stage("bookUs", "batch.book", done=True):
                done = [it for it in now if it.payload.done]
                for it in done:
                    self._finish(m, it.payload)
        with _dtel.stage("bookUs", "batch.book", done=True):
            back = [it for it in group
                    if it not in done and not it.future.done()]
            if done:
                width = max(it.payload.num for it in done)
                idx = np.zeros((len(done), width), np.int32)
                scores = np.full((len(done), width), -np.inf, np.float32)
                for row, it in enumerate(done):
                    st = it.payload
                    idx[row, :st.num] = st.slate[:st.num]
                    scores[row, :st.num] = st.conf[:st.num]
        if done:
            _deliver(done, idx, scores)
        return back or None

    # -- what a check reads ------------------------------------------------------

    def audits(self, m, kept, uid: int) -> List[Dict[str, Any]]:
        """Of the kept dispatches, those whose audited row was
        ``uid``'s, oldest first: a ``round`` (``pos0``, ``rows``,
        ``fixed``, ``taken``: the slate before it; of every pass the
        block's input ``ids``, ``masked``, the ``logits`` of every item
        at every row, the rows ``picked``, the router's ``picks``; the result's ``tokens`` /
        ``conf`` / ``when``; of the commit pass the written ``k`` / ``v``
        rows, the layers' inputs ``x``, ``picks`` / ``gates``) or an
        ``events`` commit (``len0``, ``ids``, and the same five of the
        committed tokens)."""
        import jax

        out = []
        for audit, meta in kept:
            if meta["uid"] != int(uid):
                continue
            host = jax.device_get(audit)
            got = dict(meta)
            if meta["kind"] == "round":
                # the row's own passes (the dispatch ran its longest
                # row's)
                p = min(int(host["passes"]), int(np.max(meta["when"])) + 1)
                got.update(
                    passes=[{"ids": host["ids"][i],
                             "masked": host["masked"][i],
                             "picked": host["picked"][i],
                             "picks": host["routed"][i],
                             "logits": host["logits"][i][:, :m.n_items]}
                            for i in range(p)])
            got.update({k: host[k] for k in ("k", "v", "x", "picks",
                                             "gates")})
            out.append(got)
        return out

    def report(self, m) -> Dict[str, Any]:
        return {"scratchBlocks": self._scratch_blocks,
            "blockLength": self.spec.block_len,
            "slateBatches": list(SLATE_BATCHES)}
