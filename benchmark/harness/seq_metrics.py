"""What the sequence cell's per-layer metrics share: the traced call's
step and encode programs out of ``readers["trace_scopes"]``
(``harness/seq_trace.py``), and the operations the traced call needed
(``harness/shapes_seq.py``). Everything returns None for a cell, a
program or a run that has no such trace or work."""

from benchmark.harness import seq_trace, shapes, shapes_seq


def work(r):
    w = r.get("work") or {}
    return w if w.get("kind") == "seq_train_calls" else None


def module(r, name):
    t = r.get("trace_scopes")
    if not t or work(r) is None:
        return None
    found = [m for k, m in t.items() if k.startswith(name)]
    return found[0] if found and found[0]["count"] else None


def traced_tokens(w):
    """(step tokens, encode tokens) of the traced calls, with the
    passes each takes: a step is forward + backward (3), an encode
    forward alone (1)."""
    n = w["traced_calls"]
    return n * w["steps"] * w["step_tokens"], n * w["encode_tokens"]


def traced_pairs(w):
    """(query, key) pairs inside segments: the encode sees every packed
    row once; a step's rows are a sample of them, taken at the mean."""
    per_token = shapes_seq.segment_pairs(w["segment_lengths"]) \
        / w["encode_tokens"]
    step_tokens, encode_tokens = traced_tokens(w)
    return per_token * step_tokens, per_token * encode_tokens


def kernel_seconds(r, *scopes):
    """Device seconds of the Pallas kernels under ``scopes`` in both
    programs of the traced call."""
    total = 0.0
    for name in (seq_trace.STEP_MODULE, seq_trace.ENCODE_MODULE):
        m = module(r, name)
        if m is not None:
            total += seq_trace.under(m["kernels"], *scopes)
    return total or None


def roofline_share(r, need, seconds):
    if not seconds:
        return None
    peak = shapes.peaks(r["device"]["kind"])
    return 100.0 * shapes.least_time(need["flops"], need["bytes"],
                                     peak)["seconds"] / seconds
