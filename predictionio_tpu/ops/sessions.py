"""The session lane: a sequence backbone served from per-user caches.

:class:`SessionTopK` is a :class:`~predictionio_tpu.ops.serving.
DeviceTopK` (the pattern is ``TwoStageTopK``: a subclass, its own
``BatchLane`` in the shared ``BatchDispatcher``, its rows in the AOT
ladder) whose store holds, beside the output table ``Y``:

- the weights of the BACKBONE it is handed (:func:`backbone_of`: the
  ``glm_moe_dsa`` block, :class:`GlmBackbone` over ``ops/mla.py``; the
  ``sdar_moe`` block, ``ops/slates.py::SdarBackbone`` over
  ``ops/sdar.py``; the ``smallthinker`` block,
  :class:`SmallThinkerBackbone` over ``ops/smallthinker.py``; the
  ``qwen3_next`` block, :class:`Qwen3NextBackbone` over
  ``ops/qwen3next.py``; the ``falcon_h1`` block,
  :class:`FalconH1Backbone` over ``ops/falconh1.py``);
- ``X``: every user's LAST hidden state (final norm applied), so that
  the inherited ``users`` lane answers a query without new events;
- a POOL of cache blocks: per layer one array ``[blocks, bs, width]``
  for every per-token cache row the backbone DECLARES (``cache_rows``:
  GLM-5's latent and index key; SDAR's and SmallThinker's key and
  value rows), under one block table and one free list a LAYER KIND
  the backbone declares (``kinds``: :class:`LayerKind`: which layers,
  and how many trailing positions a layer of that kind reads; None:
  all). A block id names a session's rows of every ``cache_rows``
  entry in every layer OF ITS KIND. A kind that keeps ``keep``
  positions gives a session's oldest blocks back as its end moves on:
  a block that lies wholly before ``length - keep + 1`` is read by no
  later query, so a window layer's table starts at the oldest block
  the session still holds. GLM-5 and SDAR declare ONE kind that keeps
  everything; SmallThinker a global and a window kind. Block 0 of
  every kind is never handed out: padding writes land there.
- SLOTS, for a kind of layer that keeps no row a token but one
  constant-size STATE a session (``LayerKind.state``: Qwen3-Next's
  Gated DeltaNet layers: a float32 recurrent state and a convolution's
  tail): per layer of that kind one array ``[slots, ...]`` for every
  array the kind names. A slot is to such a kind what a block is to
  the others, and a session holds exactly ONE, from its first cached
  event on: the same free list, admission (a session is admitted when
  EVERY kind can take it), release and eviction; slot 0 is never
  handed out (a padded query row names it). A program overwrites a
  slot in place, so what a slot holds is only ever valid TOGETHER with
  its session's ``length``: the device arrays are swapped first, the
  length is booked after them, and a dispatch that fails in between
  forgets its sessions (:meth:`SessionTopK._forget`: the next touch
  prefills them again from the host's events), so that no query ever
  runs on a state ahead of its session's length.
- BOTH in one layer: the kinds' layer lists may OVERLAP. Falcon-H1's
  every layer runs attention heads and Mamba-2 heads side by side, so
  its block kind (key and value rows) and its slot kind (the state-space
  state and the convolution's tail) name the SAME layers: the cache
  rows' arrays are indexed by a layer's place among the layers of its
  BLOCK kind, a slot kind's arrays by its place in that slot kind, a
  query row carries one block table AND one slot id that the same
  layer's program reads, and every count (``memory_report()``,
  ``session_report()``) takes a layer once a kind.

The manager keeps the blocks, the tables, allocation, release and
eviction over all kinds (a session leaves every kind at once), the
waves two queries of one user take, the ladder and the lane; the
backbone brings its prefill-chunk program, the programs of its queries
with their ladder entries, the lane's dispatch function and its
audits.

GLM-5's query ``(user, new events, k)`` appends the events to the
user's session and recommends: ONE dispatch runs the backbone over the
group's new tokens against the caches, writes their cache rows, scores
the output table, masks what the user has seen and takes the top-k,
fetched as one packed buffer. Queries of one user in one group are
applied in arrival order, each in a wave of its own, so every answer
reflects exactly its own prefix. (SDAR's query takes several rounds:
``ops/slates.py``.) A user without a session is prefilled from the
history the model stored (the backbone's chunk program), which is also
how :meth:`warmup` builds the resident sessions at deploy time. A
backbone that commits cache rows in whole blocks (``commit_multiple``)
leaves a session's newest events as ids: its provisional TAIL. When
the pool is full the session touched longest ago gives up its blocks;
its events stay on the host and its next touch prefills it again.

Host-side bookkeeping (block tables, lengths, the events themselves)
lives under ``_sess_lock``; the device tables are swapped under
``_store_lock`` exactly as ``patch_users`` swaps its own.
"""

from __future__ import annotations

import collections
import functools
import logging
import threading
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

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


class LayerKind(NamedTuple):
    """A kind of layer a backbone declares: the layers of that kind
    and what a session holds there: BLOCKS of the backbone's
    ``cache_rows`` (``keep``: how many trailing positions a layer of
    that kind reads; None: all) or, with ``state``, one SLOT of the
    arrays it names: ``(name, shape, dtype, the component's name in
    memory_report())``. A layer belongs to at most ONE block kind and
    to any number of slot kinds: a block kind and a slot kind may name
    the same layers (a layer with two memories)."""

    name: str
    layers: Tuple[int, ...]
    keep: Optional[int]
    state: Tuple[Tuple[str, Tuple[int, ...], str, str], ...] = ()


def one_kind(n_layers: int) -> Tuple[LayerKind, ...]:
    """Every layer of one kind that keeps everything."""
    return (LayerKind("all", tuple(range(n_layers)), None),)


@functools.lru_cache(maxsize=None)     # (asked for every query row formed)
def kind_layout(kinds, T: int, S: int, bs: int
                ) -> Tuple[Tuple[Tuple[int, int, int, int], ...], int]:
    """Where a row of ``ints`` (``[3 header, item ids x T, ...]``)
    holds each kind's part, kinds in order: ``(cache rows to write x
    T, [a kind that keeps a window: the position of its table's first
    row,] its block table)``. Returns per kind ``(offset of the cache
    rows, of the base | -1, of the table, the table's blocks)`` and the
    row's width. A kind that keeps everything has ``S / bs`` blocks; a
    window kind the ``T`` rows' and the ``keep - 1`` positions before
    them, wherever they fall in their blocks. One kind that keeps all:
    ``3 + 2T + S / bs``, the layout the lane has always had. A SLOT
    kind adds one id, its session's slot: ``(-1, -1, its offset, 1)``,
    a table of that one entry and no cache rows."""
    out, at = [], 3 + T
    for kind in kinds:
        if kind.state:
            out.append((-1, -1, at, 1))
            at += 1
            continue
        nb = S // bs
        if kind.keep is not None:
            nb = min(nb, -(-(kind.keep + T) // bs) + 1)
        table = at + T + (kind.keep is not None)
        out.append((at, -1 if kind.keep is None else at + T, table, nb))
        at = table + nb
    return tuple(out), at


class _Session:
    __slots__ = ("items", "events", "length", "held", "first", "touched",
                 "inflight")

    def __init__(self, items: np.ndarray, kinds: int = 1):
        self.items = np.asarray(items, dtype=np.int32)
        self.events = len(self.items)   # events the session holds
        self.length = 0          # events whose rows are in the cache
        # a layer kind each: the blocks it holds, oldest first, and how
        # many older ones it has given back (a window kind's)
        self.held: List[List[int]] = [[] for _ in range(kinds)]
        self.first = [0] * kinds
        self.touched = 0
        self.inflight = 0        # queries between two of their rounds

    @property
    def blocks(self) -> List[int]:
        """The first kind's blocks (the only kind's of most
        backbones)."""
        return self.held[0]

    def append(self, items) -> None:
        """``items`` behind the session's events (ids only: the caller
        says what of them is cached)."""
        n = self.events + len(items)
        if n > len(self.items):
            grown = np.zeros(max(2 * len(self.items), n, 64), np.int32)
            grown[:self.events] = self.items[:self.events]
            self.items = grown
        self.items[self.events:n] = items
        self.events = n


def waves(steps: Dict[int, list]):
    """``{user: [its steps in arrival order]}`` -> lists of ``(user,
    step)``: the first step of every user, then the second of those
    that have one, ... so that one dispatch never holds two steps of
    one user and each sees exactly its own prefix."""
    wave = 0
    while True:
        rows = [(u, s[wave]) for u, s in steps.items() if len(s) > wave]
        if not rows:
            return
        yield rows
        wave += 1


def _dispatch_sess_group(srv: "SessionTopK",
                         group: List[_Pending]) -> None:
    """Session queries -> one dispatch a WAVE (:func:`waves`). A
    step is a query's (at most ``SESS_EVENTS``) new events; a query
    with more is cut into steps of which only the last answers."""

    with _dtel.stage("bookUs", "batch.book"):
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
    for rows in waves(steps):
        for lo in range(0, len(rows), SESS_MAX_BATCH):
            part = rows[lo:lo + SESS_MAX_BATCH]
            wi, ws = srv.extend([(u, st[0]) for u, st in part], kb)
            with _dtel.stage("bookUs", "batch.book", done=True):
                for j, (_, st) in enumerate(part):
                    if st[1] is not None:
                        idx[st[1]], scores[st[1]] = wi[j], ws[j]
    _deliver(group, idx, scores)


class GlmBackbone:
    """GLM-5's block (``ops/mla.py``) as the session lane serves it:
    two cache rows a token and layer (the latent and the indexer's
    key), a query answered by ONE extend dispatch, events committed one
    by one."""

    entries = ("sess", "sesspre")
    commit_multiple = 1
    max_batch = SESS_MAX_BATCH
    max_positions = 0       # events a session may hold (0: the ladder's)
    dispatch = staticmethod(_dispatch_sess_group)

    def __init__(self, params):
        from predictionio_tpu.ops import mla

        self.spec = spec = mla.glm_spec(params)
        self.width = spec.width
        self.compute_dtype = spec.compute_dtype
        self.kinds = one_kind(spec.n_layers)
        # (name, width, the component's name in memory_report())
        self.cache_rows = (("lat", spec.lat_width, "sessionLatents"),
                           ("ik", spec.idx_dim, "sessionIndexKeys"))
        # the shortest cached-length bucket is this many selections
        # (``index_topk``) long; a prefill chunk is one selection
        self.floor = SESS_FLOOR_SELECTIONS * spec.idx_topk
        self.chunk = spec.idx_topk
        self.qb = min(32, self.chunk)

    def serving_theta(self, theta):
        from predictionio_tpu.ops import mla

        return mla.serving_theta(theta, self.spec)

    def draw_theta(self, V: int, params):
        from predictionio_tpu.ops import mla

        return mla.draw_serving_theta(V, params)

    def extend_program(self, m: "SessionTopK", kb: int, S: int):
        def make():
            import jax

            from predictionio_tpu.ops import mla

            def sess_extend(theta, X, seen_bits, pool, Y, ints):
                packed, X, seen_bits, lat, ik, audit = mla.extend_step(
                    theta, X, seen_bits, pool["lat"], pool["ik"], Y, ints,
                    spec=self.spec, kb=kb, T=SESS_EVENTS, S=S, bs=m._bs,
                    n_items=m.n_items, mode=m._mode, mask_seen=True,
                    audit=bool(m._audit_keep))
                return packed, X, seen_bits, {"lat": lat, "ik": ik}, audit

            return jax.jit(sess_extend, donate_argnums=(1, 2, 3))

        return m._program(("sess", kb, S), make)

    def prefill_program(self, m: "SessionTopK", S: int):
        def make():
            import jax

            from predictionio_tpu.ops import mla

            def sess_prefill(theta, X, pool, ints):
                X, lat, ik, h = mla.prefill_chunk(
                    theta, X, pool["lat"], pool["ik"], ints,
                    spec=self.spec, C=self.chunk, S=S, bs=m._bs,
                    qb=self.qb)
                return X, {"lat": lat, "ik": ik}, h

            return jax.jit(sess_prefill, donate_argnums=(1, 2))

        return m._program(("sesspre", S), make)

    def plan(self, m: "SessionTopK", kb: int) -> List[Tuple]:
        """``("sess", kb, bb, S)`` for every (query bucket,
        cached-length bucket) at the largest k bucket."""
        return [("sess", kb, bb, S) for bb in SESS_BATCHES
                for S in m._s_buckets]

    def lower(self, m: "SessionTopK", entry: Tuple, tables, theta, pool):
        import jax
        import jax.numpy as jnp

        _, kb, bb, S = entry
        return lower_compile(
            self.extend_program(m, kb, S), theta, tables["X"],
            tables["seen_bits"], pool, tables["Y"],
            jax.ShapeDtypeStruct(
                (bb, m._ints_width(SESS_EVENTS, S)), jnp.int32))

    def warm(self, m: "SessionTopK", entry: Tuple) -> None:
        _, kb, bb, S = entry
        self._run_extend(m, np.zeros(
            (bb, m._ints_width(SESS_EVENTS, S)), np.int32)
            + m._pad_row(SESS_EVENTS, S), kb, S, n=0)

    def _run_extend(self, m: "SessionTopK", ints: np.ndarray, kb: int,
                    S: int, n: int):
        """One extend dispatch; returns the fetched packed buffer and
        the (device) audit outputs (None from a lane built without
        ``audit``)."""
        got = {}

        def take(out):
            packed, m._X, m._seen_bits, m._pool, got["audit"] = out
            return packed

        bb = ints.shape[0]
        out = m._dispatch_entry(
            ("sess", kb, bb, S), lambda: self.extend_program(m, kb, S),
            lambda: (m._theta, m._X, m._seen_bits, m._pool, m._Y, ints),
            batch=n, bucket=bb, take=take)
        with _dtel.stage("fetchUs", "dispatch.fetch", done=True):
            host = np.asarray(out)
        return host, got["audit"]

    def extend(self, m: "SessionTopK", rows: List[Tuple[int, np.ndarray]],
               kb: int) -> Tuple[np.ndarray, np.ndarray]:
        n = len(rows)
        T = SESS_EVENTS
        with m._sess_lock, _trace_span(
                "sess.extend", attributes={"queries": n}):
            busy = {int(u) for u, _ in rows}
            if len(busy) != n:
                raise ValueError("one dispatch takes one query a user")
            # (outside every stage: a user without a session is
            # prefilled here, in dispatches with records of their own)
            sessions = [m._ensure_session(int(u), busy) for u, _ in rows]
            with _dtel.stage("formUs", "batch.form"):
                for sess, (_, items) in zip(sessions, rows):
                    m._reserve(sess, sess.length + len(items), busy)
                S = m._s_bucket(max(s.length + len(it) for s, (_, it)
                                    in zip(sessions, rows)))
                bb = next(b for b in SESS_BATCHES if b >= n)
                ints = np.tile(m._pad_row(T, S), (bb, 1))
                for j, (sess, (u, items)) in enumerate(
                        zip(sessions, rows)):
                    k = len(items)
                    ints[j, 0], ints[j, 1], ints[j, 2] = u, sess.length, k
                    ints[j, 3:3 + k] = items
                    m._kind_fill(ints[j], sess, T, S, sess.length, k)
            try:
                host, audit = self._run_extend(m, ints, kb, S, n)
                idx, scores = self._book(m, sessions, rows, host, audit,
                                         bb, kb)
            except BaseException:
                # the program may have run: a slot is then AHEAD of its
                # session's length
                m._forget(busy)
                raise
        return idx[:n], scores[:n]

    def _book(self, m: "SessionTopK", sessions, rows, host, audit, bb: int,
              kb: int):
        """After an extend dispatch: the sessions' events and lengths,
        the audit, the counters; returns the unpacked result."""
        T = SESS_EVENTS
        with _dtel.stage("bookUs", "batch.book", done=True):
            m._clock += 1
            tokens = 0
            for sess, (u, items) in zip(sessions, rows):
                if len(items):
                    sess.append(items)
                    sess.length = sess.events
                    tokens += len(items)
                    m._trim(sess, sess.length)
                sess.touched = m._clock
            if audit is not None and (m._watched is None or any(
                    int(u) in m._watched for u, _ in rows)):
                m._audits.append((audit, bb, [
                    (int(u), sess.length) for sess, (u, _)
                    in zip(sessions, rows)]))
            if tokens:
                _metrics.SESS_TOKENS.inc(amount=tokens, program="extend")
            for kind, rows_ in (("valid", tokens),
                                ("padded", bb * T - tokens)):
                _metrics.SESS_TOKEN_ROWS.inc(amount=rows_, kind=kind)
                m._token_rows[kind] += rows_
            _metrics.SESS_POSITIONS.inc(
                amount=sum(s_.length for s_ in sessions))
            m._note_state_traffic(sum(1 for _, items in rows if len(items)))
            self._book_counters(
                *(float(c) for c in host[0, 2 * kb:].view(np.float32)))
            return _unpack(host[:, :2 * kb], kb)

    @staticmethod
    def _book_counters(selected, eligible, local, touched) -> None:
        """The counters that rode behind a dispatch's packed columns
        (``mla.extend_step``)."""
        if eligible > 0:
            _metrics.SESS_SELECTED_SHARE.set(selected / eligible)
            _metrics.SESS_SELECTED.inc(amount=selected, kind="selected")
            _metrics.SESS_SELECTED.inc(amount=eligible, kind="eligible")
        if local > 0:
            _metrics.SESS_LOCAL_PICKS.inc(amount=local)
        if touched > 0:
            _metrics.SESS_EXPERTS_TOUCHED.inc(amount=touched)

    def audits(self, m: "SessionTopK", kept, uid: int
               ) -> List[Dict[str, Any]]:
        import jax

        out = []
        for audit, bb, rows in kept:
            for slot, (u, length) in enumerate(rows):
                if u != int(uid):
                    continue
                host = jax.device_get(audit)
                got = {k: v[:, slot] for k, v in host.items()
                       if k != "scores"}
                got.update(scores=host["scores"][slot][:m.n_items],
                           length=int(length), slot=slot,
                           queries=len(rows), bucket=int(bb))
                out.append(got)
        return out

    def report(self, m: "SessionTopK") -> Dict[str, Any]:
        """``tokenRows``: the token rows dispatched, valid and padded;
        ``skippedRowShare``: the share of them that the loop which cuts
        and attends never runs (``mla.mla_select_attend``)."""
        rows = dict(m._token_rows)
        return {"tokenRows": rows, "skippedRowShare": rows["padded"]
                / max(sum(rows.values()), 1)}


SWA_CHUNK = 2048        # tokens a prefill chunk of SmallThinker holds


class SmallThinkerBackbone(GlmBackbone):
    """SmallThinker's block (``ops/smallthinker.py``) as the session
    lane serves it: key and value rows a token and layer, layers of TWO
    kinds (global layers keep every position, window layers the
    ``sliding_window_size`` newest), a query answered by ONE extend
    dispatch as GLM-5's is, events committed one by one, a session held
    to the model's ``max_position_embeddings``."""

    def __init__(self, params):
        from predictionio_tpu.ops import smallthinker

        self.spec = spec = smallthinker.swa_spec(params)
        self.width = spec.width
        self.compute_dtype = spec.compute_dtype
        self.kinds = tuple(LayerKind(*k) for k in spec.kinds)
        self.max_positions = spec.max_positions
        self.cache_rows = (("k", spec.kv_width, "sessionKeys"),
                           ("v", spec.kv_width, "sessionValues"))
        # a prefill chunk: a quarter of the longest session's bucket at
        # most; the shortest cached-length bucket is four chunks
        self.chunk = max(4, min(
            SWA_CHUNK, _bucket(max(spec.max_positions, 16)) // 4))
        self.floor = 4 * self.chunk
        self.qb = min(32, self.chunk)

    # the programs' names on the device: jit_swa_extend, jit_swa_prefill
    program_prefix = "swa"

    @staticmethod
    def _ops():
        """The module that holds the block's programs."""
        from predictionio_tpu.ops import smallthinker

        return smallthinker

    def serving_theta(self, theta):
        return self._ops().serving_theta(theta, self.spec)

    def draw_theta(self, V: int, params):
        return self._ops().draw_serving_theta(V, params)

    def extend_program(self, m: "SessionTopK", kb: int, S: int):
        def make():
            import jax

            ops = self._ops()

            def extend(theta, X, seen_bits, pool, Y, ints):
                return ops.extend_step(
                    theta, X, seen_bits, pool, Y, ints, spec=self.spec,
                    kb=kb, T=SESS_EVENTS, S=S, bs=m._bs, n_items=m.n_items,
                    mode=m._mode, layout=m._kind_layout(SESS_EVENTS, S),
                    audit=bool(m._audit_keep))

            extend.__name__ = f"{self.program_prefix}_extend"
            return jax.jit(extend, donate_argnums=(1, 2, 3))

        return m._program(("sess", kb, S), make)

    def prefill_program(self, m: "SessionTopK", S: int):
        def make():
            import jax

            ops = self._ops()

            def prefill(theta, X, pool, ints):
                return ops.prefill_chunk(
                    theta, X, pool, ints, spec=self.spec, C=self.chunk,
                    S=S, bs=m._bs, qb=self.qb,
                    layout=m._kind_layout(self.chunk, S))

            prefill.__name__ = f"{self.program_prefix}_prefill"
            return jax.jit(prefill, donate_argnums=(1, 2))

        return m._program(("sesspre", S), make)

    @staticmethod
    def _book_counters(read_global, read_window, touched, _spare) -> None:
        """``smallthinker.extend_step``'s counters."""
        for kind, rows in (("global", read_global), ("window", read_window)):
            if rows > 0:
                _metrics.SESS_ROWS_READ.inc(amount=rows, kind=kind)
        if touched > 0:
            _metrics.SESS_EXPERTS_TOUCHED.inc(amount=touched)


LIN_CHUNK = 2048        # tokens a prefill chunk of Qwen3-Next holds


class Qwen3NextBackbone(SmallThinkerBackbone):
    """Qwen3-Next's block (``ops/qwen3next.py``) as the session lane
    serves it: layers of TWO kinds of which only one holds rows: the
    gated attention layers (every ``full_attention_interval``-th) key
    and value rows in BLOCKS, every position kept; the Gated DeltaNet
    layers one SLOT a session (a float32 state and the convolution's
    tail), advanced in place by every dispatch. A query is answered by
    ONE extend dispatch, events committed one by one, a session held to
    the model's ``max_position_embeddings``."""

    program_prefix = "lin"

    @staticmethod
    def _ops():
        from predictionio_tpu.ops import qwen3next

        return qwen3next

    def _shape(self, params):
        """``(spec, positions a chunk of the chunked form holds, tokens
        a prefill chunk holds at most)``."""
        ops = self._ops()
        return ops.lin_spec(params), ops.GDN_CHUNK, LIN_CHUNK

    def __init__(self, params):
        self.spec, scan_chunk, tokens = self._shape(params)
        spec = self.spec
        self.width = spec.width
        self.compute_dtype = spec.compute_dtype
        self.kinds = tuple(LayerKind(*k) for k in spec.kinds)
        self.max_positions = int(params.max_seq_len)
        self.cache_rows = (("k", spec.kv_width, "sessionKeys"),
                           ("v", spec.kv_width, "sessionValues"))
        # a prefill chunk: whole chunks of the chunked form, a quarter
        # of the longest session's bucket at most; the shortest
        # cached-length bucket is four chunks
        self.chunk = max(scan_chunk, min(
            tokens, _bucket(max(self.max_positions, 16)) // 4))
        self.floor = 4 * self.chunk
        self.qb = min(32, self.chunk)

    @staticmethod
    def _book_counters(read_full, touched, found, made) -> None:
        """``qwen3next.extend_step``'s counters."""
        for counter, amount, labels in (
                (_metrics.SESS_ROWS_READ, read_full, {"kind": "full"}),
                (_metrics.SESS_EXPERTS_TOUCHED, touched, {}),
                (_metrics.SESS_LOCAL_PICKS, found, {}),
                (_metrics.SESS_PICKS_MADE, made, {})):
            if amount > 0:
                counter.inc(amount=amount, **labels)


HYB_CHUNK = 2048        # tokens a prefill chunk of Falcon-H1 holds


class FalconH1Backbone(Qwen3NextBackbone):
    """Falcon-H1's block (``ops/falconh1.py``) as the session lane
    serves it: TWO kinds over the SAME layers: every layer holds key
    and value rows in BLOCKS (every position kept) AND one SLOT a
    session (the Mamba-2 heads' float32 state and the convolution's
    tail), advanced in place by every dispatch. A query is answered by
    ONE extend dispatch, events committed one by one, a session held to
    the model's ``max_position_embeddings``."""

    program_prefix = "hyb"

    @staticmethod
    def _ops():
        from predictionio_tpu.ops import falconh1

        return falconh1

    def _shape(self, params):
        spec = self._ops().hyb_spec(params)
        return spec, spec.chunk, HYB_CHUNK

    @staticmethod
    def _book_counters(read, *_spare) -> None:
        """``falconh1.extend_step``'s counters."""
        if read > 0:
            _metrics.SESS_ROWS_READ.inc(amount=read, kind="attn")


def backbone_of(params):
    """The backbone that serves ``params.block`` from per-user
    caches."""
    if params.block == "glm_moe_dsa":
        return GlmBackbone(params)
    if params.block == "sdar_moe":
        from predictionio_tpu.ops.slates import SdarBackbone

        return SdarBackbone(params)
    if params.block == "smallthinker":
        return SmallThinkerBackbone(params)
    if params.block == "qwen3_next":
        return Qwen3NextBackbone(params)
    if params.block == "falcon_h1":
        return FalconH1Backbone(params)
    raise ValueError(
        f"no session backbone for block {params.block!r}: the lane "
        "serves glm_moe_dsa, sdar_moe, smallthinker, qwen3_next and "
        "falcon_h1")


class SessionTopK(DeviceTopK):
    """See the module docstring. ``item_factors``: the output table;
    ``theta``: the backbone's parameters as served (the backbone's
    ``serving_theta`` / ``draw_theta``, without ``out_emb``);
    ``histories``: ``{user row: item ids, oldest first}``;
    ``pool_tokens``: the cache rows the pool of a layer kind that
    keeps everything holds (``SeqRecParams.session_pool_tokens``; 0:
    twice the stored histories); a kind that keeps a window gets the
    share of it that the stored histories' blocks under the window are
    of their blocks whole, so both kinds fill alike;
    ``audit``: how many dispatches' audits the lane keeps
    (``SeqRecParams.session_audit``; 0: the programs compute none; see
    :meth:`audits`); ``backbone``: :func:`backbone_of` ``(params)``
    when None. A block holds ``SESS_BLOCK`` rows; the programs are
    laddered over the backbone's query buckets and over the cached
    length in powers of two from the backbone's ``floor`` to twice the
    longest stored history's bucket (the backbone's ``max_positions``
    at most: a session that an append would take past it is refused)."""

    def __init__(self, item_factors, theta: Dict[str, Any], params,
                 n_users: int, histories: Optional[Dict[int, Any]] = None,
                 seen: Optional[Dict[int, np.ndarray]] = None,
                 pool_tokens: int = 0, audit: int = 0,
                 microbatch: Optional[bool] = None, backbone=None):
        import jax
        import jax.numpy as jnp

        self._bb = bb = backbone or backbone_of(params)
        self._spec = bb.spec
        n_users = int(n_users)
        X = np.zeros((n_users, bb.width), dtype=np.float32)
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
        self._kinds: Tuple[LayerKind, ...] = tuple(bb.kinds)
        self._windowed = [k for k, kind in enumerate(self._kinds)
                          if kind.keep is not None]
        lo = _bucket(bb.floor, lo=self._bs)
        longest = max((len(h) for h in self._histories.values()), default=0)
        self._s_max = 2 * _bucket(longest, lo=lo)
        if bb.max_positions:
            self._s_max = max(lo, min(self._s_max,
                                      _bucket(bb.max_positions, lo=lo)))
        self._s_buckets = []
        s = lo
        while s <= self._s_max:
            self._s_buckets.append(s)
            s *= 2
        self._chunk = bb.chunk
        stored = sum(len(h) for h in self._histories.values())
        tokens = int(pool_tokens) or max(2 * stored, 4 * lo)
        whole = max(2, -(-tokens // self._bs))
        # what the stored histories hold of each kind once resident
        need = np.sum([self._blocks_of(len(h)) for h in
                       self._histories.values()] or
                      [[0] * len(self._kinds)], axis=0)
        def sized(k: int, kind: LayerKind, n: int) -> int:
            """Blocks (slots) kind ``k``'s pool holds beside its
            spare, ``n`` of them held by the stored histories."""
            if kind.state:
                # as many sessions as the block pool is sized for
                return max(2, -(-whole * int(n) // int(need[0]))
                           if need[0] else whole * self._bs // self._s_max)
            if kind.keep is None or not need[0]:
                return whole
            return max(-(-whole * int(n) // int(need[0])),
                       self._blocks_of(self._s_max, chunk=self._chunk)[k] + 1)

        self._kind_blocks = [1 + sized(k, kind, n) for k, (kind, n)
                             in enumerate(zip(self._kinds, need))]
        # a layer's BLOCK kind (a layer a slot kind alone names has
        # none and holds no cache rows); a slot kind's arrays are
        # indexed by a layer's place in that kind's own list
        self._layer_kind: Dict[int, int] = {}
        for k, kind in enumerate(self._kinds):
            for i in (() if kind.state else kind.layers):
                if i in self._layer_kind:
                    raise ValueError(
                        f"layer {i} is in two block kinds: one table "
                        "names a session's rows in a layer")
                self._layer_kind[i] = k
        self._slotted = any(kind.state for kind in self._kinds)
        cache_dtype = jnp.dtype(bb.compute_dtype)
        with self._store_lock, _trace_span("store.upload"):
            # a pool array a layer THAT HOLDS IT, in layer order: the
            # cache rows in the layers of the block kinds, a slot
            # kind's arrays in its own (the same layers, where a layer
            # has both)
            self._pool = {
                name: tuple(jnp.zeros(
                    (self._kind_blocks[self._layer_kind[i]], self._bs,
                     width), cache_dtype)
                    for i in sorted(self._layer_kind))
                for name, width, _ in bb.cache_rows}
            for k, kind in enumerate(self._kinds):
                for name, shape, dtype, _ in kind.state:
                    if name in self._pool:
                        raise ValueError(f"two pool arrays named {name!r}")
                    self._pool[name] = tuple(
                        jnp.zeros((self._kind_blocks[k],) + tuple(shape),
                                  jnp.dtype(dtype)) for _ in kind.layers)
            jax.block_until_ready(self._pool)
        self._sess_lock = threading.RLock()
        self._sessions: Dict[int, _Session] = {}
        # a free list a kind; block 0 of each is the spare
        self._frees = [list(range(n - 1, 0, -1)) for n in self._kind_blocks]
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
                "pio-microbatch-sess", max_batch=bb.max_batch,
                dispatch_fn=bb.dispatch)
        _metrics.SESS_CACHE_CAPACITY.set(self._row_layers(
            [n - 1 for n in self._kind_blocks]))
        for kind, n in zip(self._kinds, self._kind_blocks):
            if kind.state:
                _metrics.SESS_STATE_CAPACITY.set(n - 1)
                _metrics.SESS_STATE_SLOT_BYTES.set(self._slot_bytes(kind))
        self._note_fill()

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

    def _program(self, key: Tuple, make):
        """The backbone's jitted program under ``key``, made once."""
        prog = self._sess_programs.get(key)
        if prog is None:
            prog = self._sess_programs[key] = make()
        return prog

    def _kind_layout(self, T: int, S: int) -> Tuple:
        return kind_layout(self._kinds, T, S, self._bs)[0]

    def _ints_width(self, T: int, S: int) -> int:
        return kind_layout(self._kinds, T, S, self._bs)[1]

    def _store_sig(self, tables: Dict[str, Any]) -> Tuple:
        # (by name: a dict comes back from a program with sorted keys)
        first = self._pool[self._bb.cache_rows[0][0]][0]
        return super()._store_sig(tables) + (
            tuple(first.shape), str(first.dtype), self._spec,
            bool(self._audit_keep)) + tuple(self._kind_blocks[1:])

    def aot_plan(self, max_k: int = 128,
                 batch_sizes: Tuple[int, ...] = ()) -> List[Tuple]:
        """The parent ladder plus the backbone's own entries at the
        largest k bucket, and ``("sesspre", chunk, S)`` for every
        cached-length bucket."""
        plan = super().aot_plan(max_k=max_k, batch_sizes=batch_sizes)
        self._ladder_kmax = max(e[1] for e in plan if e[0] == "user")
        plan += self._bb.plan(self, self._sess_kb(1))
        plan += [("sesspre", self._chunk, S) for S in self._s_buckets]
        return plan

    def _aot_lower_entry(self, entry: Tuple, tables: Dict[str, Any]):
        import jax
        import jax.numpy as jnp

        if entry[0] not in self._bb.entries:
            return super()._aot_lower_entry(entry, tables)
        with self._store_lock:
            pool, theta = self._pool, self._theta
        if entry[0] == "sesspre":
            _, C, S = entry
            return lower_compile(
                self._bb.prefill_program(self, S), theta, tables["X"], pool,
                jax.ShapeDtypeStruct((self._ints_width(C, S),), jnp.int32))
        return self._bb.lower(self, entry, tables, theta, pool)

    def _warm_entry(self, entry: Tuple) -> None:
        if entry[0] not in self._bb.entries:
            return super()._warm_entry(entry)
        with self._sess_lock:
            if entry[0] == "sesspre":
                _, C, S = entry
                ints = np.zeros(self._ints_width(C, S), np.int32)
                ints[0] = NO_ROW
                self._run_prefill(ints, S, n=0)
            else:
                self._bb.warm(self, entry)

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
                    need = self._blocks_of(len(self._histories[u]),
                                           chunk=self._chunk)
                    if u in self._sessions or any(
                            n + 1 > len(free)
                            for n, free in zip(need, self._frees)):
                        continue
                    self._ensure_session(u, busy=())
        self._resident_s = time.perf_counter() - t0
        logger.info("session lane: ladder %s in %.1fs, %d resident "
                    "sessions (%d events) prefilled in %.1fs", stats,
                    t0 - t_start, len(self._sessions),
                    sum(s.events for s in self._sessions.values()),
                    self._resident_s)
        return stats

    # -- the cache manager -------------------------------------------------

    def _pad_row(self, T: int, S: int) -> np.ndarray:
        """A padded query row: no user row, nothing cached, nothing
        new, block 0 everywhere."""
        row = np.zeros(self._ints_width(T, S), np.int32)
        row[0] = NO_ROW
        return row

    @property
    def _free(self) -> List[int]:
        """The first kind's free list (the only kind's of most
        backbones)."""
        return self._frees[0]

    @_free.setter
    def _free(self, blocks: List[int]) -> None:
        self._frees[0] = blocks

    def _blocks_of(self, length: int, chunk: int = 0) -> List[int]:
        """Blocks a session of ``length`` cached events holds of each
        kind: all of them, or under a window the blocks that still hold
        one of the ``keep - 1`` positions before its end (``chunk``:
        while its last chunk of that many tokens is prefilled, the
        most it ever holds)."""
        whole = -(-int(length) // self._bs)
        return [min(whole, 1) if kind.state else whole
                if kind.keep is None else min(
                    whole, whole - max(0, int(length) - chunk - kind.keep + 1)
                    // self._bs) for kind in self._kinds]

    def _row_layers(self, blocks) -> float:
        """``blocks`` a kind as cache rows: row-layers summed over the
        BLOCK kinds, over their layers (one kind: its rows), so that
        the gauges count what is HELD whichever kind holds it (a slot
        kind holds no rows: its slots have gauges of their own)."""
        rows = [(n, len(kind.layers)) for n, kind in
                zip(blocks, self._kinds) if not kind.state]
        return self._bs * sum(n * layers for n, layers in rows) \
            / max(sum(layers for _, layers in rows), 1)

    @staticmethod
    def _slot_bytes(kind: LayerKind) -> int:
        """Bytes ONE slot of ``kind`` holds, over the kind's layers."""
        import jax.numpy as jnp

        return len(kind.layers) * int(sum(
            int(np.prod(shape)) * jnp.dtype(dtype).itemsize
            for _, shape, dtype, _ in kind.state))

    def _note_state_traffic(self, live: int) -> None:
        """``live`` queries of one dispatch brought events: each read
        its session's slot of every slot kind and wrote it back."""
        if not (self._slotted and live):
            return
        moved = live * sum(self._slot_bytes(kind) for kind in self._kinds)
        for way in ("read", "written"):
            _metrics.SESS_STATE_BYTES.inc(amount=moved, dir=way)

    def _held_blocks(self) -> List[int]:
        return [n - 1 - len(free)
                for n, free in zip(self._kind_blocks, self._frees)]

    def _phys(self, sess: _Session, pos: np.ndarray, kind: int = 0
              ) -> np.ndarray:
        blocks = np.asarray(sess.held[kind], dtype=np.int64)
        return (blocks[pos // self._bs - sess.first[kind]] * self._bs
                + pos % self._bs).astype(np.int32)

    def _kind_fill(self, row: np.ndarray, sess: _Session, T: int, S: int,
                   pos0: int, n: int) -> None:
        """Every kind's part of a row of ``ints`` (:func:`kind_layout`)
        for ``n`` new rows at positions ``pos0 ..`` of ``sess``."""
        pos = np.arange(pos0, pos0 + n)
        for k, (w, b, t, nb) in enumerate(self._kind_layout(T, S)):
            if w >= 0:
                row[w:w + n] = self._phys(sess, pos, k)
            if b >= 0:
                row[b] = sess.first[k] * self._bs
            have = min(len(sess.held[k]), nb)
            row[t:t + have] = sess.held[k][:have]

    def _note_fill(self) -> None:
        held = self._held_blocks()
        _metrics.SESS_CACHE_TOKENS.set(self._row_layers(held))
        for kind, n in zip(self._kinds, held):
            if kind.state:
                _metrics.SESS_STATE_SLOTS.set(n)
            else:
                _metrics.SESS_KIND_TOKENS.set(n * self._bs, kind=kind.name)

    def _make_room(self, need, keep: Optional[_Session], busy) -> None:
        """``need`` free blocks (a count a kind; an int: of the first
        kind), evicting the sessions touched longest ago (never
        ``keep``, one of ``busy`` or one with a query between two of
        its rounds) when fewer are free. A session leaves every kind
        at once."""
        if isinstance(need, int):
            need = [need] + [0] * (len(self._kinds) - 1)
        while any(len(free) < n for free, n in zip(self._frees, need)):
            victims = [(s.touched, u) for u, s in self._sessions.items()
                       if s is not keep and u not in busy and any(s.held)
                       and not s.inflight]
            if not victims:
                raise RuntimeError(
                    f"the session pool ({[n - 1 for n in self._kind_blocks]}"
                    f" blocks of {self._bs}) cannot hold {list(need)} more "
                    "blocks beside the sessions of this dispatch")
            self.release(min(victims)[1])
            _metrics.SESS_EVICTIONS.inc()

    def _reserve(self, sess: _Session, length: int, busy) -> None:
        """Blocks for ``length`` cached events in every kind
        (:meth:`_make_room`)."""
        whole = -(-int(length) // self._bs)
        need = [max(0, (min(whole, 1) if kind.state else whole) - first
                    - len(held)) for kind, first, held in
                zip(self._kinds, sess.first, sess.held)]
        if not any(need):
            return
        with _trace_span("sess.cache_alloc",
                         attributes={"blocks": sum(need)}):
            self._make_room(need, sess, busy)
            for held, free, n in zip(sess.held, self._frees, need):
                held += [free.pop() for _ in range(n)]
            self._note_fill()

    def _trim(self, sess: _Session, floor: int) -> None:
        """The next rows of ``sess`` lie at positions ``floor ..``: a
        kind that keeps ``keep`` positions gives back the blocks that
        lie wholly before ``floor - keep + 1``."""
        released = 0
        for k in self._windowed:
            lo = max(0, int(floor) - self._kinds[k].keep + 1) // self._bs
            n = min(lo - sess.first[k], len(sess.held[k]))
            if n > 0:
                self._frees[k] += sess.held[k][:n]
                del sess.held[k][:n]
                released += n
            sess.first[k] = max(lo, sess.first[k])
        if released:
            _metrics.SESS_BLOCKS_RELEASED.inc(amount=released)
            self._note_fill()

    def _give_back(self, sess: _Session) -> None:
        for free, held in zip(self._frees, sess.held):
            free += held
        self._note_fill()

    def release(self, uid: int) -> None:
        """Give a session's blocks of every kind back; its events stay
        on the host (the next touch prefills them again)."""
        with self._sess_lock:
            sess = self._sessions.pop(int(uid), None)
            if sess is None:
                return
            self._histories[int(uid)] = sess.items[:sess.events]
            self._give_back(sess)

    def _forget(self, uids) -> None:
        """After a dispatch that failed once its program may have run:
        a slot the program advanced is AHEAD of its session's length
        (the arrays are swapped before the lengths are booked), and a
        recurrent state cannot be taken back. The dispatch's sessions
        leave the device; their next touch prefills them from the
        host's events. (Cache rows past a session's length are read by
        nobody: a lane of block kinds alone keeps its sessions.)"""
        if self._slotted:
            for u in uids:
                self.release(u)

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
        """The first kind's block table over ``S`` positions."""
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
        sess = _Session(hist, len(self._kinds))
        self._sessions[uid] = sess
        self._prefill(sess, uid, busy)
        return sess

    def _prefill(self, sess: _Session, row: int, busy):
        """The session's events, in whole multiples of the backbone's
        ``commit_multiple`` (the rest stays its tail), through the
        prefill program into blocks of the pool, a chunk at a time; the
        last chunk leaves the last cached event's hidden state in user
        row ``row`` (``NO_ROW``: in none). Returns that state (device),
        None for nothing cached."""
        C, h_last = self._chunk, None
        n_all = sess.events - sess.events % self._bb.commit_multiple
        hist = sess.items[:n_all]
        if n_all == 0:
            return None
        with _trace_span("sess.prefill", attributes={
                "events": n_all, "chunks": -(-n_all // C)}):
            # room for the most it will hold, made before the first
            # chunk; the blocks are taken a chunk at a time, a window
            # kind giving back what the next chunk no longer reads
            self._make_room(self._blocks_of(n_all, chunk=C), sess, busy)
            for p0 in range(0, n_all, C):
                n = min(C, n_all - p0)
                S = self._s_bucket(p0 + C)
                self._trim(sess, p0)
                self._reserve(sess, p0 + n, busy)
                ints = np.zeros(self._ints_width(C, S), np.int32)
                final = p0 + n == n_all
                ints[0] = row if final else NO_ROW
                ints[1], ints[2] = p0, n
                ints[3:3 + n] = hist[p0:p0 + n]
                self._kind_fill(ints, sess, C, S, p0, n)
                h_last = self._run_prefill(ints, S, n=n)
            sess.length = n_all
            self._trim(sess, n_all)
        return h_last

    def encode(self, items) -> np.ndarray:
        """The last hidden state (final norm applied, float32) of a
        history that is NOBODY's session yet: prefilled into blocks
        that are given back at once, and written to no user row. What
        the fold-in writes for a user the store has no row for."""
        items = np.asarray(items, dtype=np.int32)
        self._s_bucket(len(items))
        with self._sess_lock:
            sess = _Session(items, len(self._kinds))
            try:
                h = self._prefill(sess, NO_ROW, busy=())
            finally:
                self._give_back(sess)
        return np.zeros(self._bb.width, np.float32) if h is None \
            else np.asarray(h, dtype=np.float32)

    def _run_prefill(self, ints: np.ndarray, S: int, n: int):
        def take(out):
            self._X, self._pool, h = out
            return h

        out = self._dispatch_entry(
            ("sesspre", self._chunk, S),
            lambda: self._bb.prefill_program(self, S),
            lambda: (self._theta, self._X, self._pool, ints),
            batch=n, bucket=self._chunk, take=take)
        if n:
            _metrics.SESS_TOKENS.inc(amount=n, program="prefill")
        return out

    # -- serving -----------------------------------------------------------

    def extend(self, rows: List[Tuple[int, np.ndarray]],
               kb: int) -> Tuple[np.ndarray, np.ndarray]:
        """(A backbone whose query is one dispatch:) append each
        ``(user row, new events)`` (distinct users, at most
        ``SESS_EVENTS`` events each) and return ``(item ids [n, kb],
        scores [n, kb])``: one dispatch."""
        return self._bb.extend(self, rows, kb)

    def sess_topk(self, uid: int, items, k: int
                  ) -> Tuple[np.ndarray, np.ndarray]:
        """Append ``items`` (item rows, oldest first; may be empty) to
        ``uid``'s session and return ``(item rows, scores)``: its top
        ``k``, seen items masked, or from a backbone that generates
        slates the slate of ``k`` items in position order, each with
        its confidence. Concurrent callers share dispatches through
        the ``pio-microbatch-sess`` lane."""
        items = np.asarray(items, dtype=np.int32).reshape(-1)
        with _trace_span("device.sess_topk",
                         attributes={"k": int(k), "events": len(items)}) \
                as sp:
            if self._sess_batcher is not None:
                return self._sess_batcher.submit((int(uid), items), int(k),
                                                 span=sp)
            item = _Pending((int(uid), items), int(k), 0.0, 0, 0.0)
            item.future.set_running_or_notify_cancel()
            group = [item]
            while group:        # a query of several rounds comes back
                group = self._bb.dispatch(self, group)
            res, row = item.future.result()
            return res.render(row, int(k))

    # -- what the lane knows of a session ----------------------------------

    def session_events(self, uid: int) -> np.ndarray:
        """The events ``uid``'s session holds (a copy), oldest first:
        the cached ones and its tail."""
        with self._sess_lock:
            sess = self._sessions.get(int(uid))
            if sess is None:
                return np.array(self._histories.get(
                    int(uid), np.zeros(0, np.int32)))
            return np.array(sess.items[:sess.events])

    def cached_length(self, uid: int) -> int:
        """Events of ``uid`` whose rows are in the cache (0: no
        session)."""
        with self._sess_lock:
            sess = self._sessions.get(int(uid))
            return 0 if sess is None else int(sess.length)

    def last_hidden(self, uid: int) -> np.ndarray:
        """``uid``'s last hidden state (final norm applied), float32:
        its row of the store's user table."""
        with self._store_lock:
            row = self._X[int(uid)]
        return np.asarray(row, dtype=np.float32)

    def session_state(self, uid: int) -> Optional[Dict[str, Any]]:
        """What ``uid``'s SLOT holds now, fetched: ``{"length", and a
        slot kind's array by name: [the kind's layers, ...]}``; None
        for a lane without slot kinds or a user without a cached
        event. Only with the lane idle is it the state AS OF
        ``length``."""
        with self._sess_lock, self._store_lock:
            sess = self._sessions.get(int(uid))
            out: Dict[str, Any] = {}
            for k, kind in enumerate(self._kinds):
                if not kind.state or sess is None or not sess.held[k]:
                    continue
                for name, _, _, _ in kind.state:
                    out[name] = np.stack([np.asarray(
                        a[sess.held[k][0]].astype("float32"))
                        for a in self._pool[name]])
            return dict(out, length=int(sess.length)) if out else None

    def session_rows(self, uid: int) -> Optional[Dict[str, Any]]:
        """What ``uid``'s BLOCKS hold now in the kinds that keep every
        position, fetched through the session's own block list:
        ``{"length", and a cache row by name: [the kinds' layers,
        length, width]}`` in the cache's dtype, row ``p`` position
        ``p``'s; None for a user without a cached event. A kind that
        keeps all never rewrites a row, so what is read once at the end
        is what every earlier dispatch read of its prefix."""
        import jax.numpy as jnp

        with self._sess_lock, self._store_lock:
            sess = self._sessions.get(int(uid))
            if sess is None or not sess.length:
                return None
            held = sorted(self._layer_kind)
            out: Dict[str, Any] = {}
            for name, width, _ in self._bb.cache_rows:
                out[name] = np.stack([np.asarray(a[jnp.asarray(
                    sess.held[self._layer_kind[i]])].reshape(
                        -1, width)[:sess.length])
                    for a, i in zip(self._pool[name], held)
                    if self._kinds[self._layer_kind[i]].keep is None])
            return dict(out, length=int(sess.length))

    def watch(self, uids=None) -> None:
        """Keep the audits of dispatches that answer one of ``uids``
        only (None: of every dispatch), and drop those kept so far."""
        with self._sess_lock:
            self._watched = None if uids is None else {int(u) for u in uids}
            self._audits.clear()

    def audits(self, uid: int) -> List[Dict[str, Any]]:
        """What the lane computed for ``uid`` in the dispatches whose
        audit it still keeps (the latest ``audit`` of them; oldest
        first), fetched from the device. GLM-5's answer: ``length``
        (the events it reflects), ``scores`` (every item's, before the
        seen mask) and, for the query's last event, ``layers`` (the
        residual stream after every layer), ``selected`` (the
        positions a layer attended over), ``lat`` / ``ik`` (the two
        cache rows a layer wrote), ``picks`` / ``gates`` / ``h2`` (an
        expert layer's router picks, their weights, and the router's
        input); ``slot``, ``queries`` and ``bucket`` say where in which
        dispatch it rode. SDAR's: ``ops/slates.py``. Empty when the
        lane was built without ``audit``."""
        with self._sess_lock:
            kept = list(self._audits)
        return self._bb.audits(self, kept, uid)

    def close(self) -> None:
        """Release the dispatcher AND the pool's device memory, and
        unload the lane's programs (they hold their scratch): the lane
        serves nothing afterwards; the weights stay readable."""
        super().close()
        with self._sess_lock, self._store_lock:
            for arrays in self._pool.values():
                for a in arrays:
                    if not a.is_deleted():
                        a.delete()
            self._pool = {name: () for name in self._pool}
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
            held = self._held_blocks()
            live = list(self._sessions.values())
            return dict(
                self._bb.report(self),
                sessions=len(live), blockTokens=self._bs,
                cacheTokens=int(self._row_layers(held)),
                capacityTokens=int(self._row_layers(
                    [n - 1 for n in self._kind_blocks])),
                kinds=[dict({"name": kind.name, "layers": len(kind.layers),
                             "keep": kind.keep, "blocks": n - 1, "held": h},
                            **({"slotBytes": self._slot_bytes(kind)}
                               if kind.state else {}))
                       for kind, n, h in zip(self._kinds,
                                             self._kind_blocks, held)],
                events=int(sum(s.events for s in live)),
                tailTokens=int(sum(s.events - s.length for s in live)),
                lengthBuckets=list(self._s_buckets),
                residentSeconds=self._resident_s)

    def memory_report(self) -> Dict[str, Any]:
        report = super().memory_report()
        with self._store_lock:
            theta, pool = self._theta, self._pool
        dtype = self._bb.compute_dtype
        extra = {"backbone": {"bytes": int(sum(v.nbytes for v in
                                               theta.values())),
                              "scaleBytes": 0, "dtype": dtype}}
        held = [(name, component, dtype)
                for name, _, component in self._bb.cache_rows]
        held += [(name, component, kind_dtype) for kind in self._kinds
                 for name, _, kind_dtype, component in kind.state]
        for name, component, of in held:
            # (a pool array is counted once, under its component: the
            # kinds may share layers, never an array)
            entry = extra.setdefault(
                component, {"bytes": 0, "scaleBytes": 0, "dtype": of})
            entry["bytes"] += int(sum(a.nbytes for a in pool[name]))
        report["components"].update(extra)
        report["totalBytes"] += sum(c["bytes"] for c in extra.values())
        report["sessions"] = self.session_report()
        return report
