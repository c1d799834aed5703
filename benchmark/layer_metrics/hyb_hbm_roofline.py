"""Share of the HBM roofline a dispatch of the hybrid-session lane
reaches, in percent: the bytes a mean dispatch of the traced slice must
stream (``shapes_hyb.dispatch_bytes``: every weight of the dense model,
the slots read and written back, the cached rows read, the rows
written, the output table) at the chip's peak bandwidth, over the
extend program's device time a dispatch."""
from benchmark.harness import hyb_metrics as _h
from benchmark.harness import shapes_hyb


def read(r):
    got = _h.sliced(r)
    if got is None:
        return None
    m, w = got
    return _h.roofline(r, 0.0, shapes_hyb.dispatch_bytes(w, w["block"]),
                       m["seconds"] / m["count"])
