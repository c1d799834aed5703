"""99th percentile of (sent - due) over the window's requests, on the
generator's own clock: when this is large, a late generator and not
the server made the tail."""


def read(r):
    return (r.get("loadgen") or {}).get("gen_late_p99_ms")
