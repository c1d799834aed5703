"""New events a dispatch of the session lane ran through the backbone,
mean over the window: ``pio_sess_tokens_total{program="extend"}`` over
the lane's dispatches."""
from benchmark.harness import sess_metrics as _s


def read(r):
    w = _s.work(r)
    return None if w is None else w["tokens"] / float(w["dispatches"])
