"""Share of its roofline the indexer reaches, in percent: the index
keys a mean dispatch's queries see (counters and device time of the
traced slice's own dispatches), read once a query and layer, and
the (token, head, key) products over them, over the device time under
``sess/index`` and ``sess/select`` (scores, mask, selection)."""
from benchmark.harness import sess_metrics as _s
from benchmark.harness import shapes_sess


def read(r):
    w = _s.slice_work(r)
    seconds = _s.scope_seconds_per_dispatch(r, "sess/index", "sess/select")
    if w is None or not seconds:
        return None
    n, b = float(w["dispatches"]), w["block"]
    return _s.roofline(r, shapes_sess.index_flops(w["eligible"] / n, b),
                       shapes_sess.index_key_bytes(w["positions"] / n, b),
                       seconds)
