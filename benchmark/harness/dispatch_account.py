"""The dispatcher's own account of its thread and of its queries, read
out of the flight records (``utils/device_telemetry.py``): the records
that carry the whole tiling (``otherUs``: every microsecond of the gap
before a program call is in a named stage or in that remainder), the
``lives`` of the queries delivered after them, and the split of the
device's idle time in the traced slice by what the dispatcher thread
was doing meanwhile. A program that keeps no such account (an older
commit run under this benchmark) gives empty lists and None, and the
metric is left out of the line."""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchmark.harness import program_spans


def accounted(r) -> List[Dict[str, Any]]:
    """The window's batched dispatches whose record tiles its gap."""
    return [x for x in program_spans.batched(r)
            if x.get("otherUs") is not None]


def lives(r) -> List[Dict[str, Any]]:
    """Every query delivered in the window, as its dispatcher stamped
    it: ``{firstWaitUs, rounds, ridingUs, betweenUs}``."""
    return [life for x in r.get("flight") or []
            for life in x.get("lives") or ()]


def stage_p50(r, field: str) -> Optional[float]:
    return program_spans.median_of(accounted(r), lambda x: x.get(field))


def life_p50(r, field: str) -> Optional[float]:
    return program_spans.median_of(lives(r), lambda life: life.get(field))


def idle_shares(r) -> Optional[Dict[str, float]]:
    """Of the traced slice's idle device seconds (``window_s - busy_s``
    of the reduced trace), the percent that the ONE dispatcher thread
    spent, over the records written in the slice (``ts``: at ready):

    - ``no_work``: asleep with every lane empty (``gapIdleUs``);
    - ``window``: asleep on a batching window (``gapWindowUs``);
    - ``host``: the rest of its gaps (fetch, deliver, book, pick, form,
      lock, other);
    - ``call_wait``: inside ``enqueueUs + deviceUs`` while the chip was
      not busy: the program call, the launch, the waiter's wake-up.

    A thread that blocks on every program tiles the slice as gap +
    enqueue + device, and the chip is busy only inside enqueue +
    device, so the four add up to 100 less the two dispatches cut by
    the slice's ends. None without a trace, without the account, or
    when more than one dispatcher thread wrote records in the slice
    (their gaps would overlap)."""
    t = r.get("trace")
    if not t or t.get("profile_start_epoch_s") is None:
        return None
    t0 = t["profile_start_epoch_s"]
    t1 = t0 + t["window_s"]
    idle_us = (t["window_s"] - t["busy_s"]) * 1e6
    recs = [x for x in accounted(r) if t0 <= x["ts"] < t1]
    if not recs or idle_us <= 0 \
            or len({x.get("dispatcher") for x in recs}) != 1:
        return None
    no_work = sum(x["gapIdleUs"] for x in recs)
    window = sum(x["gapWindowUs"] for x in recs)
    host = sum(x["gapUs"] for x in recs) - no_work - window
    call_wait = sum(x["enqueueUs"] + x["deviceUs"] for x in recs) \
        - t["busy_s"] * 1e6
    return {name: 100.0 * us / idle_us for name, us in (
        ("no_work", no_work), ("window", window), ("host", host),
        ("call_wait", call_wait))}


def idle_share(r, name: str) -> Optional[float]:
    shares = idle_shares(r)
    return None if shares is None else shares[name]
