"""1 - (union of device-op intervals) / (traced slice), in percent,
from the profiler trace of a steady slice of the window."""


def read(r):
    t = r.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
