"""95th percentile of the client-side latency from the due time, over
the requests due in the window (a failure counted at the time limit).
Reported beside the judged ``query_p50_ms`` so that a change in the
shape of the distribution shows."""


def read(r):
    return (r.get("loadgen") or {}).get("query_p95_ms")
