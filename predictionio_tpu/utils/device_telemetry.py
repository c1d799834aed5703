"""Device-plane flight recorder: per-dispatch telemetry for live serving.

The host plane has been observable since PR 2/4 (metrics + trace trees),
but every DEVICE-side question was unanswerable: how much device time a
dispatch cost, whether it hit the AOT ladder or fell back to jit, how
full the batch was, how long it queued. This module is the bounded,
thread-safe ring those answers live in — the ALX-style per-step
device-time accounting, applied to the serving plane:

- every device dispatch (user top-k, batched users, item similarity,
  the fold-in solve) records one :class:`DispatchRecord`: lane, k/batch
  bucket shape, batch size + fill ratio, store precision, kernel lane
  (fused Pallas vs XLA chain), AOT ladder result (``hit`` /
  ``miss_jit`` / ``jit`` for unladdered programs), queue wait, host
  wall µs and **device µs** — the dispatch-to-``block_until_ready``
  window on the monotonic clock;
- a batching dispatcher's thread works strictly one dispatch after
  another, so its records also carry **stage stamps** that tile its
  time (:func:`stage`, :func:`record_dispatch`): ``gapUs`` from the
  previous dispatch's ``block_until_ready`` return to this dispatch's
  program call, of which ``gapIdleUs`` asleep with every lane empty,
  ``gapWindowUs`` asleep on a batching window, ``formUs`` forming the
  batch and ``lockWaitUs`` acquiring the store lock; then
  ``enqueueUs`` (the program call), ``deviceUs``, and after the record
  is written ``fetchUs`` and ``deliverUs`` (and, from the fetched
  result of a fused-kernel dispatch, ``selectRounds``:
  :func:`note_select_rounds`). Over one ``dispatcher``
  thread's consecutive records ``gapUs + enqueueUs + deviceUs`` adds
  up to the wall clock. Each stage is also a profiler annotation
  (``batch.idle``, ``batch.window``, ``batch.form``, ``dispatch.lock``,
  ``dispatch.enqueue``, ``dispatch.wait``, ``dispatch.fetch``,
  ``batch.deliver``) on the dispatcher thread's line of a capture;
- the ring is bounded (``PIO_DEVICE_TELEMETRY_RING``, default 2048):
  a long-lived server holds the last N dispatches, never all of them
  (evictions are counted, not silently dropped);
- surfaces: ``GET /dispatches.json`` on the query server (snapshot +
  per-lane summary), the ``pio_dispatch_device_seconds`` histogram,
  ``device.execute`` child spans in the PR-4 trace tree (Perfetto shows
  device time under each ``device.*`` span), and ``pio top``;
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
from typing import Any, Dict, List, Optional

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
    "mark_ready",
    "note_select_rounds",
]


def _env_enabled() -> bool:
    return os.environ.get("PIO_DEVICE_TELEMETRY", "1").strip().lower() \
        not in ("0", "off", "false")


def _env_capacity(default: int = 2048) -> int:
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
        self._lock = threading.Lock()
        self._ring: collections.deque = collections.deque(
            maxlen=self.capacity)
        self._recorded = 0
        # the most recent record taken by THIS thread — how a batching
        # dispatcher hands the dispatch record to the result object
        # without changing the users_topk return signature
        self._tls = threading.local()

    # -- write side --------------------------------------------------------

    def record(self, rec: Dict[str, Any]) -> Dict[str, Any]:
        with self._lock:
            self._ring.append(rec)
            self._recorded += 1
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
        return {"recorded": recorded, "retained": retained,
                "evicted": recorded - retained,
                "capacity": self.capacity}

    def summary(self) -> Dict[str, Any]:
        """Per-lane aggregates over the retained window: dispatch count,
        device/host-µs percentiles, queue-wait p50, mean batch fill,
        AOT hit/miss counts — the compact view ``pio top`` and the bench
        artifacts embed."""
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
            aot = collections.Counter(r.get("aot", "?") for r in rs)
            out[lane] = {
                "dispatches": len(rs),
                "deviceUsP50": pct(dev, 0.50),
                "deviceUsP99": pct(dev, 0.99),
                "hostUsP50": pct(host, 0.50),
                "hostUsP99": pct(host, 0.99),
                "queueWaitUsP50": pct(waits, 0.50),
                "meanFill": round(sum(fills) / len(fills), 4)
                if fills else None,
                "aot": dict(aot),
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
                   trace_parent: Any = None,
                   queue_wait_mean_us: Optional[float] = None):
    """Bind batching context for the device dispatch(es) the block
    issues: queue wait of the oldest grouped query and the mean over
    the group, the group size, and a trace parent for the
    ``device.execute`` span (the dispatcher thread has no ambient
    trace context of its own)."""
    prior = getattr(_dispatch_ctx, "ctx", None)
    _dispatch_ctx.ctx = {"queueWaitUs": queue_wait_us, "group": group,
                         "traceParent": trace_parent,
                         "queueWaitMeanUs": queue_wait_mean_us}
    try:
        yield
    finally:
        _dispatch_ctx.ctx = prior


def current_dispatch_context() -> Optional[Dict[str, Any]]:
    return getattr(_dispatch_ctx, "ctx", None)


# -- stage stamps --------------------------------------------------------------

# Per thread: the stamps taken since the last record (``pending``; they
# belong to the record being formed), when the last dispatch's
# ``block_until_ready`` returned (``ready``, monotonic), and the
# thread's ``name/native id`` as records carry it (``thread``).
_stage = threading.local()


@contextlib.contextmanager
def stage(field: str, name: str, done: bool = False):
    """Time one stage of the calling thread's dispatch work: a profiler
    annotation ``name`` round the block, and its µs added to ``field``
    of a flight record — the one this thread is forming (merged in by
    the next :func:`record_dispatch`), or with ``done`` the one it wrote
    last (fetch and deliver happen after the record exists; the dict is
    the one :func:`last_record` and the ring hold). Killed
    (``PIO_DEVICE_TELEMETRY=0``): no clock, no annotation."""
    if not RECORDER.enabled:
        yield
        return
    t = time.monotonic()
    try:
        with _tracing.annotation(name):
            yield
    finally:
        us = (time.monotonic() - t) * 1e6
        if done:
            into = RECORDER.last()
        else:
            into = getattr(_stage, "pending", None)
            if into is None:
                into = _stage.pending = {}
        if into is not None:
            into[field] = round(into.get(field, 0.0) + us, 1)


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
                    started_epoch: Optional[float] = None,
                    interpret: Optional[bool] = None,
                    lock_wait_us: Optional[float] = None,
                    locked_us: float = 0.0,
                    called: Optional[float] = None,
                    ready: Optional[float] = None
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
    they give ``gapUs`` and move this thread's clock on."""
    if not RECORDER.enabled:
        return None
    ctx = current_dispatch_context()
    pending = getattr(_stage, "pending", None) or {}
    _stage.pending = None
    thread = getattr(_stage, "thread", None)
    if thread is None:
        thread = _stage.thread = (f"{threading.current_thread().name}"
                                  f"/{threading.get_native_id()}")
    stamps: Dict[str, Any] = {
        "enqueueUs": round(float(host_us - device_us), 1),
        "dispatcher": thread}
    if lock_wait_us is not None:
        stamps["lockWaitUs"] = round(float(lock_wait_us), 1)
    if "formUs" in pending or locked_us:
        stamps["formUs"] = round(pending.get("formUs", 0.0)
                                 + float(locked_us), 1)
    if ctx is not None and called is not None:
        # a batching dispatcher's thread: one dispatch after another
        last = getattr(_stage, "ready", None)
        stamps["gapUs"] = None if last is None \
            else round((called - last) * 1e6, 1)
        stamps["gapIdleUs"] = pending.get("gapIdleUs", 0.0)
        stamps["gapWindowUs"] = pending.get("gapWindowUs", 0.0)
        mean = ctx.get("queueWaitMeanUs")
        stamps["queueWaitMeanUs"] = None if mean is None \
            else round(float(mean), 1)
        _stage.ready = ready
    ctx = ctx or {}
    rec: Dict[str, Any] = {
        "ts": started_epoch if started_epoch is not None else time.time(),
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
    return rec
