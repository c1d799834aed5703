"""What the slate cell's per-layer metrics share: the round program's
device time by scope (``readers["slate_module"]``) and the counter
deltas over the window (``readers["slate"]``) and over the traced
slice alone (``readers["slate_slice"]``), from
``drivers/http_slates.py``. Everything returns None for a cell, a
program or a run without them (the parent commit has no such lane,
module or counter)."""

from benchmark.harness import shapes


def window(r):
    w = r.get("slate")
    return w if w and w.get("rounds") else None


def sliced(r):
    """``(module, counters)`` of the traced slice, or None."""
    m, w = r.get("slate_module"), r.get("slate_slice")
    if not m or not w or not w.get("passes_device"):
        return None
    return m, w


def under(times, part: str) -> float:
    """Seconds under the scopes that hold ``part`` (the commit pass
    runs the layer's scopes inside its own)."""
    return sum(v for k, v in times.items() if part in k)


def roofline(r, flops, bytes_, seconds):
    if not seconds:
        return None
    peak = shapes.peaks(r["device"]["kind"])
    return 100.0 * shapes.least_time(flops, bytes_, peak)["seconds"] \
        / seconds
