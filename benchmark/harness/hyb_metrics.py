"""What the hybrid-session cell's per-layer metrics share: the extend
program's device time by scope (``readers["hyb_module"]``) and the
counter deltas over the window (``readers["hyb"]``) and over the traced
slice alone (``readers["hyb_slice"]``), from
``drivers/http_sess_hybrid.py``. Everything returns None for a cell, a
program or a run without them (the parent commit has no such lane,
module or counter)."""

from benchmark.harness.slate_metrics import roofline, under  # noqa: F401


def window(r):
    w = r.get("hyb")
    return w if w and w.get("dispatches") and w.get("tokens") else None


def sliced(r):
    """``(module, counters)`` of the traced slice, or None."""
    m, w = r.get("hyb_module"), r.get("hyb_slice")
    if not m or not w or not w.get("dispatches") or not w.get("tokens"):
        return None
    return m, w


def scope_share(r, part: str):
    """Share of the extend program's device self time under the scopes
    that hold ``part``, in percent."""
    got = sliced(r)
    if got is None:
        return None
    whole = sum(got[0]["scopes"].values())
    return 100.0 * under(got[0]["scopes"], part) / whole if whole else None
