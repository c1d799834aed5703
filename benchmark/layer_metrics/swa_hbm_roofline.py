"""Share of the HBM roofline a dispatch of the mixed-session lane
reaches, in percent: the bytes a mean dispatch of the traced slice must
stream (``shapes_swa.dispatch_bytes``: attention weights, routers, the
experts PICKED, the cache rows VISIBLE, the rows written, the output
table) at the chip's peak bandwidth, over the extend program's device
time a dispatch."""
from benchmark.harness import shapes_swa
from benchmark.harness import swa_metrics as _s


def read(r):
    got = _s.sliced(r)
    if got is None:
        return None
    m, w = got
    return _s.roofline(r, 0.0, shapes_swa.dispatch_bytes(w, w["block"]),
                       m["seconds"] / m["count"])
