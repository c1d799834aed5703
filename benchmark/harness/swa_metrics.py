"""What the mixed-session cell's per-layer metrics share: the extend
program's device time by scope (``readers["swa_module"]``) and the
counter deltas over the window (``readers["swa"]``) and over the traced
slice alone (``readers["swa_slice"]``), from
``drivers/http_sess_mixed.py``. Everything returns None for a cell, a
program or a run without them (the parent commit has no such lane,
module or counter)."""

from benchmark.harness.slate_metrics import roofline, under  # noqa: F401


def window(r):
    w = r.get("swa")
    return w if w and w.get("dispatches") and w.get("tokens") else None


def sliced(r):
    """``(module, counters)`` of the traced slice, or None."""
    m, w = r.get("swa_module"), r.get("swa_slice")
    if not m or not w or not w.get("dispatches") or not w.get("tokens"):
        return None
    return m, w
