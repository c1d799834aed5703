"""Share of the chip's bf16 peak the whole dispatch reaches, in percent:
the traced dispatches' model FLOPs (``shapes_lin.model_flops``: every
new token through both kinds of mixer, router, the experts it found
here and the shared one a layer, the head a query, attention over the
cached rows) over the extend program's device time. A memory-bound
step: read beside ``lin_hbm_roofline``."""
from benchmark.harness import lin_metrics as _l
from benchmark.harness import shapes, shapes_lin


def read(r):
    got = _l.sliced(r)
    if got is None:
        return None
    m, w = got
    peak = shapes.peaks(r["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * shapes_lin.model_flops(w, w["block"]) \
        / (m["seconds"] * peak)
