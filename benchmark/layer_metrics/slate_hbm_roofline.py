"""Share of the HBM roofline a pass of the slate lane reaches, in
percent: the bytes a mean device pass of the traced slice must stream
(``shapes_slate.pass_bytes``: attention weights, routers, the experts
PICKED, the cached rows read, the output table) at the chip's peak
bandwidth, over the round program's device time a pass."""
from benchmark.harness import shapes_slate
from benchmark.harness import slate_metrics as _s


def read(r):
    got = _s.sliced(r)
    if got is None:
        return None
    m, w = got
    return _s.roofline(r, 0.0, shapes_slate.pass_bytes(w, w["block"]),
                       m["seconds"] / w["passes_device"])
