"""Share of its roofline attention over the selected latents reaches,
in percent: one latent row read and ``heads x (row + kv_rank)``
multiply-adds a selected (token, key) pair
(``shapes_sess.sparse_attention``), over the device time under
``sess/attend`` (gather, absorbed scores, softmax, weighted sum, the
value half of W_kvb and W_o are under it too and not in the need)."""
from benchmark.harness import sess_metrics as _s
from benchmark.harness import shapes_sess


def read(r):
    w = _s.slice_work(r)
    seconds = _s.scope_seconds_per_dispatch(r, "sess/attend")
    if w is None or not seconds:
        return None
    need = shapes_sess.sparse_attention(
        w["selected"] / float(w["dispatches"]), w["block"])
    return _s.roofline(r, need["flops"], need["bytes"], seconds)
