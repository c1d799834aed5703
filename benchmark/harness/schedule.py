"""The open-loop arrival schedule: a pure function of ``--seed`` and
the traffic mix's parameters (no clock, no jax).

Arrivals are a Poisson process conditioned on its count: exactly
``round(rate * seconds)`` arrivals, uniform over the window and sorted.
Gaps are exponential as in a Poisson stream, but every seed offers the
same amount of work, so ``served_qps`` does not carry the sqrt(N) noise
of a free count. The ramp before the window is drawn the same way and
is not measured.
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Mapping

import numpy as np

from benchmark.harness.data import ITEM_EXPONENT, power_law_p


def _draw_power(rng, n: int, exponent: float, size: int) -> np.ndarray:
    cdf = np.cumsum(power_law_p(n, exponent))
    return np.minimum(np.searchsorted(cdf, rng.random(size), side="right"),
                      n - 1)


def build_schedule(mix: Mapping[str, Any], n_users: int, n_items: int,
                   seed: int, seconds: float,
                   rate_qps: float = None) -> Dict[str, Any]:
    """``due`` (seconds from the window start, negative in the ramp),
    ``num`` and the request ``bodies`` (JSON bytes), in due order."""
    rate = float(mix["rate_qps"] if rate_qps is None else rate_qps)
    ramp = float(mix.get("ramp_s", 0.0))
    n_ramp, n_win = int(round(rate * ramp)), int(round(rate * seconds))
    rng = np.random.default_rng([int(seed), 2])
    due = np.concatenate([np.sort(rng.uniform(-ramp, 0.0, n_ramp)),
                          np.sort(rng.uniform(0.0, seconds, n_win))])
    n = len(due)
    users = _draw_power(rng, n_users, float(mix["user_exponent"]), n)
    is_item = rng.random(n) < float(mix.get("item_query_share", 0.0))
    nums = np.asarray([int(v) for v in mix["num"]["values"]])
    num = nums[rng.choice(len(nums), size=n, p=mix["num"]["shares"])]
    has_black = (rng.random(n) < float(mix.get("blacklist_share", 0.0))) \
        & ~is_item
    lo, hi = mix.get("blacklist_items", [1, 5])
    n_black = np.where(has_black, rng.integers(lo, hi + 1, n), 0)
    qlo, qhi = mix.get("item_query_items", [1, 3])
    n_query = np.where(is_item, rng.integers(qlo, qhi + 1, n), 0)
    extra = _draw_power(rng, n_items, ITEM_EXPONENT,
                        int(n_black.sum() + n_query.sum()))
    bodies: List[bytes] = []
    at = 0
    for i in range(n):
        k = int(n_black[i] + n_query[i])
        ids = [f"i{j}" for j in dict.fromkeys(extra[at:at + k].tolist())]
        at += k
        if is_item[i]:
            q = {"items": ids, "num": int(num[i])}
        elif k:
            q = {"user": f"u{users[i]}", "num": int(num[i]),
                 "blacklist": ids}
        else:
            q = {"user": f"u{users[i]}", "num": int(num[i])}
        bodies.append(json.dumps(q, separators=(",", ":")).encode())
    return {"due": due, "num": num, "bodies": bodies,
            "n_ramp": n_ramp, "n_window": n_win}


def save_share(path: str, sched: Mapping[str, Any], k: int, n: int) -> None:
    """Generator ``k`` of ``n`` takes every n-th request."""
    idx = np.arange(k, len(sched["due"]), n)
    blob = b"".join(sched["bodies"][i] for i in idx)
    ends = np.cumsum([len(sched["bodies"][i]) for i in idx])
    np.savez(path, index=idx, due=sched["due"][idx], num=sched["num"][idx],
             ends=ends, blob=np.frombuffer(blob, dtype=np.uint8))
