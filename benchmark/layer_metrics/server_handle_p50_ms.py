"""Median of the server's own ``pio_query_seconds`` (extract + predict
+ serve inside ``handle_query``) over the window, interpolated inside
the histogram bucket that holds the median of the bucket-count delta."""


def read(r):
    a, b = r["before"]["query_hist"], r["after"]["query_hist"]
    counts = [y - x for x, y in zip(a["counts"], b["counts"])]
    total = sum(counts)
    if total <= 0:
        return None
    target, seen = total / 2.0, 0.0
    bounds = b["bounds"]
    for i, c in enumerate(counts):
        if c and seen + c >= target:
            lo = 0.0 if i == 0 else bounds[i - 1]
            hi = bounds[i] if i < len(bounds) else lo
            return (lo + (hi - lo) * (target - seen) / c) * 1e3
        seen += c
    return None
