"""Share of the traced call's device self time spent moving rows
between token order and expert order: the ops under ``moe/dispatch``
(the plan, the gather, its transpose) and ``moe/combine`` (the
un-permutation forward, ``d_ys``, ``d_w``) in BOTH programs, step and
encode, over both programs' device time, in percent. No FLOP of the
model is in it."""

from benchmark.harness import seq_trace
from benchmark.harness import seq_metrics as _seq


def read(r):
    moved = whole = 0.0
    for name in (seq_trace.STEP_MODULE, seq_trace.ENCODE_MODULE):
        m = _seq.module(r, name)
        if m is not None:
            moved += seq_trace.under(m["scopes"], "moe/dispatch",
                                     "moe/combine")
            whole += sum(m["scopes"].values())
    return 100.0 * moved / whole if whole else None
