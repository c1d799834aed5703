"""Share of the chip's bf16 peak the whole dispatch reaches, in percent:
the traced dispatches' model FLOPs (``shapes_swa.model_flops``: every
new token through projections, router and 6 experts a layer, the head a
query, attention over the visible rows) over the extend program's
device time. A memory-bound step: read beside ``swa_hbm_roofline``."""
from benchmark.harness import shapes, shapes_swa
from benchmark.harness import swa_metrics as _s


def read(r):
    got = _s.sliced(r)
    if got is None:
        return None
    m, w = got
    peak = shapes.peaks(r["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * shapes_swa.model_flops(w, w["block"]) \
        / (m["seconds"] * peak)
