"""The session mix's open-loop schedule: ``schedule.build_schedule``'s
arrivals (Poisson conditioned on the count, a pure function of
``--seed``), every request a session query ``{"user", "items", "num"}``:
the session by the activity law over the resident users, 1 to ``max``
new events with P(n) proportional to 1/n, item ids by the popularity
law over the slice."""

from __future__ import annotations

import json
from typing import Any, Dict, List, Mapping

import numpy as np

from benchmark.harness.data import ITEM_EXPONENT
from benchmark.harness.schedule import _draw_power, save_share  # noqa: F401


def build_schedule(mix: Mapping[str, Any], n_users: int, n_items: int,
                   seed: int, seconds: float,
                   rate_qps: float = None) -> Dict[str, Any]:
    rate = float(mix["rate_qps"] if rate_qps is None else rate_qps)
    ramp = float(mix.get("ramp_s", 0.0))
    n_ramp, n_win = int(round(rate * ramp)), int(round(rate * seconds))
    rng = np.random.default_rng([int(seed), 2])
    due = np.concatenate([np.sort(rng.uniform(-ramp, 0.0, n_ramp)),
                          np.sort(rng.uniform(0.0, seconds, n_win))])
    n = len(due)
    users = _draw_power(rng, n_users, float(mix["user_exponent"]), n)
    nums = np.asarray([int(v) for v in mix["num"]["values"]])
    num = nums[rng.choice(len(nums), size=n, p=mix["num"]["shares"])]
    counts = np.arange(int(mix["events"]["min"]),
                       int(mix["events"]["max"]) + 1)
    p = 1.0 / counts
    n_events = counts[rng.choice(len(counts), size=n, p=p / p.sum())]
    items = _draw_power(rng, n_items, ITEM_EXPONENT, int(n_events.sum()))
    bodies: List[bytes] = []
    at = 0
    for i in range(n):
        k = int(n_events[i])
        q = {"user": f"u{users[i]}",
             "items": [f"i{j}" for j in items[at:at + k].tolist()],
             "num": int(num[i])}
        at += k
        bodies.append(json.dumps(q, separators=(",", ":")).encode())
    return {"due": due, "num": num, "bodies": bodies, "n_ramp": n_ramp,
            "n_window": n_win, "users": users, "n_events": n_events,
            "items": items}


def queries_of(sched: Mapping[str, Any], user: int) -> Dict[int, tuple]:
    """``{request index: its item ids}`` of the requests for ``user``."""
    ends = np.cumsum(sched["n_events"])
    return {int(i): tuple(int(v) for v in
                          sched["items"][ends[i] - sched["n_events"][i]:
                                         ends[i]])
            for i in np.flatnonzero(np.asarray(sched["users"]) == user)}


def parse_log(log, queries: Mapping[int, tuple]):
    """Cut ``log`` (item ids a session appended, in its order) into the
    ``queries`` it was sent, each query's events CONTIGUOUS and in the
    query's order, every event of the log some query's. Queries may
    have arrived in any order. Returns the indices in the order found
    (None: no such cut) by a depth-first search: two queries that start
    alike are the only choice points."""
    log = [int(v) for v in log]
    left: Dict[tuple, List[int]] = {}
    for i, q in sorted(queries.items()):
        left.setdefault(q, []).append(i)
    order: List[int] = []
    stack = [(0, iter(sorted(left)))]
    while stack:
        p, options = stack[-1]
        if p == len(log):
            return order
        for q in options:
            if left[q] and q and tuple(log[p:p + len(q)]) == q:
                order.append(left[q].pop())
                stack.append((p + len(q), iter(sorted(left))))
                break
        else:
            stack.pop()
            if order and stack:
                i = order.pop()
                left[queries[i]].append(i)
    return None
