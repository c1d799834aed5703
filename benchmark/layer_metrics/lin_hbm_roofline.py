"""Share of the HBM roofline a dispatch of the long-session lane
reaches, in percent: the bytes a mean dispatch of the traced slice must
stream (``shapes_lin.dispatch_bytes``: the mixers' weights, routers,
shared experts, the experts PICKED, the slots read and written back,
the cached rows read, the rows written, the output table) at the chip's
peak bandwidth, over the extend program's device time a dispatch."""
from benchmark.harness import lin_metrics as _l
from benchmark.harness import shapes_lin


def read(r):
    got = _l.sliced(r)
    if got is None:
        return None
    m, w = got
    return _l.roofline(r, 0.0, shapes_lin.dispatch_bytes(w, w["block"]),
                       m["seconds"] / m["count"])
