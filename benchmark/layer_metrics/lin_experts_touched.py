"""Experts whose weights a dispatch had to read, a layer, of the 128
held: ``pio_sess_experts_touched_total`` over the window's dispatches
and the layers."""
from benchmark.harness import lin_metrics as _l


def read(r):
    w = _l.window(r)
    if w is None or w.get("experts_touched") is None:
        return None
    return w["experts_touched"] / (float(w["dispatches"])
                                   * w["block"]["n_layers"])
