"""Device time of one optimizer step: the step program's module time
in the traced call over its count (forward and backward over the
step's microbatches, then Adam)."""

from benchmark.harness import seq_trace
from benchmark.harness import seq_metrics as _seq


def read(r):
    m = _seq.module(r, seq_trace.STEP_MODULE)
    return None if m is None else 1e3 * m["seconds"] / m["count"]
