"""Median ``deviceUs`` of the window's dispatch records: the HOST clock
from dispatch to ``block_until_ready``. Dispatch latency, not device
busy time (that is ``device_idle_share`` and ``topk_roofline``)."""

import statistics


def read(r):
    us = [x["deviceUs"] for x in r.get("flight") or []]
    return statistics.median(us) if us else None
