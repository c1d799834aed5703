"""Median ``queueWaitUs`` of the window's dispatch records: how long
the oldest query of a micro-batch waited for its dispatch. Absent where
no dispatch went through a batching lane (the batch job)."""

import statistics


def read(r):
    waits = [x["queueWaitUs"] for x in r.get("flight") or []
             if x.get("queueWaitUs") is not None]
    return statistics.median(waits) if waits else None
