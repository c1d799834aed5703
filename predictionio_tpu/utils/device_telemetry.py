"""Device-plane flight recorder: per-dispatch telemetry for live serving.

The host plane has been observable since PR 2/4 (metrics + trace trees),
but every DEVICE-side question was unanswerable: how much device time a
dispatch cost, whether it hit the AOT ladder or fell back to jit, how
full the batch was, how long it queued. This module is the bounded,
thread-safe ring those answers live in — the ALX-style per-step
device-time accounting, applied to the serving plane:

- every device dispatch (user top-k, batched users, item similarity,
  the session lanes' programs, the fold-in solve) leaves one record:
  lane, k/batch bucket shape, batch size + fill ratio, store precision,
  kernel lane (fused Pallas vs XLA chain), AOT ladder result (``hit`` /
  ``miss_jit`` / ``jit`` for unladdered programs), ``queueWaitUs`` (the
  AGE of the group's oldest query when the group was formed: in a lane
  that carries queries over several rounds, ``ops/slates.py``, earlier
  rounds included; a query's own wait is in ``lives``), host wall µs
  and **device µs** — the dispatch-to-``block_until_ready`` window on
  the monotonic clock. ``ts`` is ``time.time()`` when the record is
  WRITTEN, just after ``block_until_ready`` returned; ``calledTs`` and
  ``readyTs`` are the program call's start and that return on the SPAN
  clock (``tracing.span_now()``), the axis of the ``device.execute``
  span and every other span;
- a batching dispatcher's thread works strictly one dispatch after
  another, so its records also carry **stage stamps** that tile its
  time (:class:`stage`, :func:`record_dispatch`): ``gapUs`` from the
  previous dispatch's ``block_until_ready`` return to this dispatch's
  program call, of which ``gapIdleUs`` asleep with every lane empty,
  ``gapWindowUs`` asleep holding a queued query until the window a
  caller stated for it runs out (0 where none was stated: a
  dispatcher holds nothing by default), ``pickUs`` moving
  arrivals into the lanes' queues, choosing the lane and putting
  handed-back queries back, ``formUs`` forming the batch, ``bookUs``
  the bookkeeping between a program's end and the next forming (the
  record itself and its ``device.execute`` span; a lane's sessions,
  audits, counters, a slate's rows), ``lockWaitUs``
  acquiring the store lock and ``otherUs`` what is left of the gap once
  every named part is taken off (this record's, and the ``fetchUs`` /
  ``deliverUs`` / ``bookUs`` the same thread's previous record took
  after it was written): the loop itself and waits for the interpreter
  lock that fall between stages; then ``enqueueUs`` (the program call),
  ``deviceUs``, and after the record is written ``fetchUs`` and
  ``deliverUs`` (and, from the fetched result of a fused-kernel
  dispatch, ``selectRounds``: :func:`note_select_rounds`). Stages nest
  nowhere. Over one ``dispatcher`` thread's consecutive records
  ``gapUs + enqueueUs + deviceUs`` adds up to the wall clock. Each
  stage is also a profiler annotation (``batch.idle``,
  ``batch.window``, ``batch.pick``, ``batch.form``, ``batch.book``,
  ``dispatch.lock``, ``dispatch.enqueue``, ``dispatch.wait``,
  ``dispatch.fetch``, ``batch.deliver``) on the dispatcher thread's
  line of a capture;
- ``lives``: for every query a batching dispatcher delivered after this
  record was written, in the delivered group's order, ``{firstWaitUs,
  rounds, ridingUs, betweenUs}``: arrival to the first group that
  claimed it; the groups it rode in; the sum over them of claim to the
  dispatch function's return (to delivery in its last); the sum of
  hand-back to next claim. The three times add up to delivery less
  arrival (``ops/serving.py::_Pending``). A direct caller without a
  batcher (``SessionTopK.sess_topk`` with micro-batching off, the
  fold-in solve) writes no ``lives``;
- the ring is bounded (``PIO_DEVICE_TELEMETRY_RING``, default 16384:
  a measurement window of the busiest lane, about 10 MB of dicts at
  worst): a long-lived server holds the last N dispatches, never all
  of them (evictions are counted, not silently dropped); what threads
  waited for the ring's lock is counted too (``lockContended``,
  ``lockWaitedUs`` in :meth:`FlightRecorder.counts`);
- surfaces: ``GET /dispatches.json`` on the query server (snapshot +
  per-lane summary, with each stage's sum over the retained records),
  the ``pio_dispatch_device_seconds`` histogram, ``device.execute``
  child spans in the PR-4 trace tree (Perfetto shows device time under
  each ``device.*`` span), and ``pio top``;
- kill switch ``PIO_DEVICE_TELEMETRY=0``: every record site returns on
  one attribute check before touching a clock or a lock — the same
  killed-lane fast-path discipline as ``PIO_METRICS`` (PR 2), gated by
  the <5% serving-overhead bench/test either way.
"""

from __future__ import annotations

import collections
import contextlib
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from predictionio_tpu.utils import tracing as _tracing

__all__ = [
    "FlightRecorder",
    "RECORDER",
    "recorder",
    "enabled",
    "set_enabled",
    "record_dispatch",
    "last_record",
    "dispatch_scope",
    "current_dispatch_context",
    "stage",
    "STAGE_FIELDS",
    "mark_ready",
    "note_select_rounds",
]


def _env_enabled() -> bool:
    return os.environ.get("PIO_DEVICE_TELEMETRY", "1").strip().lower() \
        not in ("0", "off", "false")


def _env_capacity(default: int = 16384) -> int:
    raw = os.environ.get("PIO_DEVICE_TELEMETRY_RING", "").strip()
    try:
        cap = int(raw) if raw else default
    except ValueError:
        cap = default
    return max(16, cap)


class FlightRecorder:
    """Bounded thread-safe ring of per-dispatch telemetry records.

    Records are plain dicts (JSON-shaped at write time; the scrape path
    never touches device state). ``recorded`` counts every record ever
    taken; ``evicted`` = recorded − retained, so a scraper can tell a
    quiet server from one whose history rolled over.
    """

    def __init__(self, capacity: Optional[int] = None,
                 enabled: Optional[bool] = None):
        self.capacity = _env_capacity() if capacity is None \
            else max(16, int(capacity))
        self.enabled = _env_enabled() if enabled is None else bool(enabled)
        self._lock = _tracing.CountedLock()
        self._ring: collections.deque = collections.deque(
            maxlen=self.capacity)
        self._recorded = 0
        # the most recent record taken by THIS thread — how a batching
        # dispatcher hands the dispatch record to the result object
        # without changing the users_topk return signature
        self._tls = threading.local()

    # -- write side --------------------------------------------------------

    def record(self, rec: Dict[str, Any]) -> Dict[str, Any]:
        lock = self._lock
        if not lock.try_acquire(False):
            lock.wait()
        try:
            self._ring.append(rec)
            self._recorded += 1
        finally:
            lock.release()
        self._tls.last = rec
        return rec

    def last(self) -> Optional[Dict[str, Any]]:
        """The most recent record taken on the CALLING thread (None when
        telemetry is off or this thread never dispatched)."""
        return getattr(self._tls, "last", None)

    # -- read side ---------------------------------------------------------

    def snapshot(self, limit: int = 100) -> List[Dict[str, Any]]:
        """The newest ``limit`` records, newest first (0 -> none —
        summaries-only scrapers pass limit=0 to skip the bulk)."""
        limit = int(limit)
        if limit <= 0:
            return []
        with self._lock:
            recent = list(self._ring)[-limit:]
        return recent[::-1]

    def counts(self) -> Dict[str, int]:
        with self._lock:
            retained = len(self._ring)
            recorded = self._recorded
        lock = self._lock.stats()
        return {"recorded": recorded, "retained": retained,
                "evicted": recorded - retained,
                "capacity": self.capacity,
                "lockContended": lock["contended"],
                "lockWaitedUs": lock["waitedUs"]}

    def summary(self) -> Dict[str, Any]:
        """Per-lane aggregates over the retained window: dispatch count,
        device/host-µs percentiles, the p50 of ``queueWaitUs`` (the
        oldest grouped query's age) and of the delivered queries' own
        ``firstWaitUs``, mean batch fill, AOT hit/miss counts, and
        under ``stageUs`` the sum of every stage stamp over the lane's
        records beside ``spanSec``, the time from its oldest retained
        record to its newest — the compact view ``pio top`` and the
        bench artifacts embed."""
        with self._lock:
            records = list(self._ring)
        lanes: Dict[str, List[Dict[str, Any]]] = {}
        for r in records:
            lanes.setdefault(r.get("lane", "?"), []).append(r)

        def pct(vals: List[float], q: float) -> Optional[float]:
            if not vals:
                return None
            vals = sorted(vals)
            i = min(len(vals) - 1, int(round(q * (len(vals) - 1))))
            return round(vals[i], 1)

        out: Dict[str, Any] = {}
        for lane, rs in sorted(lanes.items()):
            dev = [r["deviceUs"] for r in rs
                   if r.get("deviceUs") is not None]
            host = [r["hostUs"] for r in rs if r.get("hostUs") is not None]
            waits = [r["queueWaitUs"] for r in rs
                     if r.get("queueWaitUs") is not None]
            fills = [r["fill"] for r in rs if r.get("fill") is not None]
            first = [life["firstWaitUs"] for r in rs
                     for life in r.get("lives") or ()]
            aot = collections.Counter(r.get("aot", "?") for r in rs)
            out[lane] = {
                "dispatches": len(rs),
                "deviceUsP50": pct(dev, 0.50),
                "deviceUsP99": pct(dev, 0.99),
                "hostUsP50": pct(host, 0.50),
                "hostUsP99": pct(host, 0.99),
                "queueWaitUsP50": pct(waits, 0.50),
                "firstWaitUsP50": pct(first, 0.50),
                "meanFill": round(sum(fills) / len(fills), 4)
                if fills else None,
                "aot": dict(aot),
                "stageUs": {f: round(sum(r.get(f) or 0.0 for r in rs), 1)
                            for f in STAGE_FIELDS},
                "spanSec": round(rs[-1]["ts"] - rs[0]["ts"], 3),
            }
        return out

    def report(self, limit: int = 100) -> Dict[str, Any]:
        """The ``GET /dispatches.json`` payload."""
        return {
            "enabled": self.enabled,
            **self.counts(),
            "summary": self.summary(),
            "dispatches": self.snapshot(limit),
        }

    def reset(self) -> None:
        with self._lock:
            self._ring.clear()
            self._recorded = 0
        self._tls = threading.local()


RECORDER = FlightRecorder()


def recorder() -> FlightRecorder:
    return RECORDER


def enabled() -> bool:
    """THE kill-switch check every dispatch site makes first — one
    attribute read, no lock, no clock (``PIO_DEVICE_TELEMETRY=0``)."""
    return RECORDER.enabled


def set_enabled(flag: bool) -> None:
    RECORDER.enabled = bool(flag)


def last_record() -> Optional[Dict[str, Any]]:
    return RECORDER.last()


# -- dispatch context --------------------------------------------------------

# What the batching dispatcher knows that the device dispatch site does
# not: how long the group queued and how many requests share the
# dispatch. Thread-local (the dispatcher calls the dispatch fn
# synchronously on its own thread), never crosses threads.
_dispatch_ctx = threading.local()


@contextlib.contextmanager
def dispatch_scope(queue_wait_us: Optional[float] = None,
                   group: Optional[int] = None,
                   trace_parent: Any = None):
    """Bind batching context for the device dispatch(es) the block
    issues: the age of the oldest grouped query (``queueWaitUs``), the
    group size, and a trace parent for the ``device.execute`` span
    (the dispatcher thread has no ambient trace context of its own)."""
    prior = getattr(_dispatch_ctx, "ctx", None)
    _dispatch_ctx.ctx = {"queueWaitUs": queue_wait_us, "group": group,
                         "traceParent": trace_parent}
    try:
        yield
    finally:
        _dispatch_ctx.ctx = prior


def current_dispatch_context() -> Optional[Dict[str, Any]]:
    return getattr(_dispatch_ctx, "ctx", None)


# -- stage stamps --------------------------------------------------------------

# every stamp of a record that is a time of the dispatcher thread, in
# the order the thread lives them (``FlightRecorder.summary`` sums each)
STAGE_FIELDS: Tuple[str, ...] = (
    "gapUs", "gapIdleUs", "gapWindowUs", "pickUs", "formUs", "bookUs",
    "lockWaitUs", "otherUs", "enqueueUs", "deviceUs", "fetchUs",
    "deliverUs")
# the stages a thread takes BEFORE a program call (``stage`` without
# ``done``), each a named part of the record's gap
_GAP_STAGES = ("gapIdleUs", "gapWindowUs", "pickUs", "formUs", "bookUs")

# Per thread: the µs of the stages taken since the last record that
# belong to the record being formed (``pending``) and of those that
# belonged to the record written last (``after``: they are parts of
# the NEXT record's gap), when the last dispatch's
# ``block_until_ready`` returned (``ready``, monotonic), and the
# thread's ``name/native id`` as records carry it (``thread``).
_stage = threading.local()


class stage:
    """Time one stage of the calling thread's dispatch work (``with
    stage(field, name): ...``): a profiler annotation ``name`` round
    the block, and its µs added to ``field`` of a flight record — the
    one this thread is forming (merged in by the next
    :func:`record_dispatch`), or with ``done`` the one it wrote last
    (fetch, deliver and a lane's bookkeeping happen after the record
    exists; the dict is the one :func:`last_record` and the ring
    hold). Stages do not nest, and no dispatch is recorded inside one:
    :func:`record_dispatch` takes every stage since the thread's last
    record as a part of the gap before the program call. Killed
    (``PIO_DEVICE_TELEMETRY=0``): no clock, no annotation. A class
    and not a generator, as ``tracing.span`` is: a dispatch passes
    through ten of these."""

    __slots__ = ("field", "name", "done", "_t", "_annotation")

    def __init__(self, field: str, name: str, done: bool = False):
        self.field = field
        self.name = name
        self.done = done

    def __enter__(self) -> None:
        if not RECORDER.enabled:
            self._t = None
            return
        self._t = time.monotonic()
        self._annotation = _tracing.annotation(self.name)
        self._annotation.__enter__()

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._t is None:
            return
        self._annotation.__exit__(exc_type, exc, tb)
        us = (time.monotonic() - self._t) * 1e6
        if self.done:
            _stage.after = getattr(_stage, "after", 0.0) + us
            into = RECORDER.last()
            if into is not None:
                into[self.field] = round(
                    into.get(self.field, 0.0) + us, 1)
        else:
            into = getattr(_stage, "pending", None)
            if into is None:
                into = _stage.pending = {}
            into[self.field] = into.get(self.field, 0.0) + us


def mark_ready() -> None:
    """A dispatcher thread starts its clock: the first record's
    ``gapUs`` runs from here, so the thread's records tile its whole
    life and not only the time after its first dispatch."""
    if RECORDER.enabled:
        _stage.ready = time.monotonic()


def note_select_rounds(rounds: Optional[int]) -> None:
    """The fused kernel's selection rounds for the dispatch this thread
    recorded last, read out of its fetched result: ``selectRounds`` on
    that record and ``pio_topk_select_rounds_total``. None (an XLA
    chain, which counts none) and a killed recorder change nothing."""
    rec = RECORDER.last() if RECORDER.enabled else None
    if rounds is None or rec is None:
        return
    rec["selectRounds"] = rounds = int(rounds)
    from predictionio_tpu.utils import metrics

    metrics.TOPK_SELECT_ROUNDS.inc(rounds, lane=rec["lane"])


def note_fields(**fields) -> None:
    """Fields a lane read out of its fetched result, on the record this
    thread wrote last (a slate round's ``passes``, ``tokensUnmasked``,
    ``carried``, ``lengthBucket``). A killed recorder changes
    nothing."""
    rec = RECORDER.last() if RECORDER.enabled else None
    if rec is not None:
        rec.update(fields)


def record_dispatch(*, lane: str, kernel: str, precision: str, aot: str,
                    k_bucket: int, batch: int, bucket: int,
                    host_us: float, device_us: float,
                    interpret: Optional[bool] = None,
                    lock_wait_us: Optional[float] = None,
                    locked_us: float = 0.0,
                    called: Optional[float] = None,
                    ready: Optional[float] = None,
                    called_ts: Optional[float] = None
                    ) -> Optional[Dict[str, Any]]:
    """Record one device dispatch (caller already paid the timing; this
    is pure bookkeeping). Returns the record dict, or None when the
    recorder is disabled. Also feeds ``pio_dispatch_device_seconds``
    and ``pio_aot_cache_requests_total`` — both behind the PR-2 metrics
    switch independently of this recorder's own kill switch.

    ``lock_wait_us`` (acquiring the store lock), ``locked_us`` (under
    it before the program call: executable lookup and ``args_fn``,
    counted as forming) and the monotonic ``called`` / ``ready`` (the
    program call's start, ``block_until_ready``'s return) are the
    stage stamps of a laddered dispatch; with a batching context bound
    they give ``gapUs`` and ``otherUs`` and move this thread's clock
    on; the time from ``ready`` to this function's end (the record's
    own writing) is the first ``bookUs`` booked to it. ``called_ts``
    is ``called`` on the span clock (``tracing.span_now()``): the
    record's ``calledTs``, and with ``host_us`` its ``readyTs``. The
    record's ``ts`` is ``time.time()`` now: it is written at ready."""
    if not RECORDER.enabled:
        return None
    ctx = current_dispatch_context()
    pending = getattr(_stage, "pending", None) or {}
    _stage.pending = None
    after = getattr(_stage, "after", 0.0)
    _stage.after = 0.0
    thread = getattr(_stage, "thread", None)
    if thread is None:
        thread = _stage.thread = (f"{threading.current_thread().name}"
                                  f"/{threading.get_native_id()}")
    stamps: Dict[str, Any] = {
        "enqueueUs": round(float(host_us - device_us), 1),
        "dispatcher": thread}
    if called_ts is not None:
        stamps["calledTs"] = float(called_ts)
        stamps["readyTs"] = float(called_ts) + float(host_us) / 1e6
    if lock_wait_us is not None:
        stamps["lockWaitUs"] = round(float(lock_wait_us), 1)
    if locked_us:
        pending["formUs"] = pending.get("formUs", 0.0) + float(locked_us)
    if ctx is not None and called is not None:
        # a batching dispatcher's thread: one dispatch after another,
        # so every stage since its last record is a part of this gap
        last = getattr(_stage, "ready", None)
        gap = None if last is None else (called - last) * 1e6
        for field in _GAP_STAGES:
            pending.setdefault(field, 0.0)
        stamps["gapUs"] = None if gap is None else round(gap, 1)
        stamps["otherUs"] = None if gap is None else round(
            gap - after - sum(pending[f] for f in _GAP_STAGES)
            - float(lock_wait_us or 0.0), 1)
        _stage.ready = ready
    stamps.update((field, round(us, 1)) for field, us in pending.items())
    ctx = ctx or {}
    rec: Dict[str, Any] = {
        "ts": time.time(),
        "lane": lane,
        "kernel": kernel,
        "precision": precision,
        "aot": aot,
        "kBucket": int(k_bucket),
        "batch": int(batch),
        "bucket": int(bucket),
        "fill": round(batch / bucket, 4) if bucket else None,
        "queueWaitUs": None if ctx.get("queueWaitUs") is None
        else round(float(ctx["queueWaitUs"]), 1),
        "hostUs": round(float(host_us), 1),
        "deviceUs": round(float(device_us), 1),
        **stamps,
    }
    if interpret is not None:
        # Pallas lanes only: False = the Mosaic-compiled kernel ran,
        # True = the interpreter did (any platform but TPU)
        rec["interpret"] = bool(interpret)
    RECORDER.record(rec)
    from predictionio_tpu.utils import metrics

    metrics.DISPATCH_DEVICE_SECONDS.observe(
        device_us / 1e6, lane=lane, kernel=kernel, precision=precision)
    if ready is not None:
        # the record's own writing is the first thing booked to it
        _stage.after = us = (time.monotonic() - ready) * 1e6
        rec["bookUs"] = round(rec.get("bookUs", 0.0) + us, 1)
    return rec
