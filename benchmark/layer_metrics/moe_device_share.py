"""Share of the step program's device self time spent under ``moe/*``
(router, dispatch, the grouped matmuls forward and backward, combine),
in percent."""

from benchmark.harness import seq_trace
from benchmark.harness import seq_metrics as _seq


def read(r):
    m = _seq.module(r, seq_trace.STEP_MODULE)
    if m is None:
        return None
    whole = sum(m["scopes"].values())
    return 100.0 * seq_trace.under(m["scopes"], "moe/") / whole \
        if whole else None
