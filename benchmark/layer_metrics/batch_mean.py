"""Queries per device dispatch over the window: the flight recorder's
``batch`` field averaged over the window's records (every lane; the
batch job's direct ``users_topk`` dispatches included, which the
micro-batch lanes' own counters do not see)."""


def read(r):
    recs = r.get("flight") or []
    return sum(x["batch"] for x in recs) / len(recs) if recs else None
