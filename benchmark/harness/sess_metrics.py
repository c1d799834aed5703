"""What the session cell's per-layer metrics share: the extend
program's device time by scope out of ``readers["trace_scopes"]``
(``seq_trace.by_module_and_scope``) and the window's counter deltas
(``readers["work"]``, and ``readers["work_slice"]`` over the traced
slice alone, from ``drivers/http_sessions.py``). Everything
returns None for a cell, a program or a run without them (the parent
commit has no such lane, module or counter)."""

from benchmark.harness import seq_trace, shapes

EXTEND_MODULE = "jit_sess_extend"


def work(r, key="work"):
    w = r.get(key) or {}
    return w if w.get("kind") == "http_sessions" and w.get("dispatches") \
        else None


def slice_work(r):
    """The counters' deltas over the TRACED SLICE (its own ends, its own
    dispatches): what the slice's device time is divided by."""
    return work(r, "work_slice")


def module(r):
    t = r.get("trace_scopes")
    if not t or work(r) is None:
        return None
    found = [m for k, m in t.items() if k.startswith(EXTEND_MODULE)]
    if not found:
        return None
    out = {"seconds": sum(m["seconds"] for m in found),
           "count": sum(m["count"] for m in found), "scopes": {}}
    for m in found:
        for k, v in m["scopes"].items():
            out["scopes"][k] = out["scopes"].get(k, 0.0) + v
    return out if out["count"] else None


def scope_seconds_per_dispatch(r, *prefixes):
    m = module(r)
    if m is None:
        return None
    s = seq_trace.under(m["scopes"], *prefixes)
    return s / m["count"] if s else None


def scope_share(r, *prefixes):
    m = module(r)
    if m is None:
        return None
    whole = sum(m["scopes"].values())
    return 100.0 * seq_trace.under(m["scopes"], *prefixes) / whole \
        if whole else None


def roofline(r, flops, bytes_, seconds):
    if not seconds:
        return None
    peak = shapes.peaks(r["device"]["kind"])
    return 100.0 * shapes.least_time(flops, bytes_, peak)["seconds"] \
        / seconds
