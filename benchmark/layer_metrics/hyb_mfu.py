"""Share of the chip's bf16 peak the whole dispatch reaches, in percent:
the traced dispatches' model FLOPs (``shapes_hyb.model_flops``: every
new token through the attention heads' projections, the Mamba-2 mixer
and the SwiGLU of every layer, the head a query, attention over the
cached rows) over the extend program's device time. A memory-bound
step: read beside ``hyb_hbm_roofline``."""
from benchmark.harness import hyb_metrics as _h
from benchmark.harness import shapes, shapes_hyb


def read(r):
    got = _h.sliced(r)
    if got is None:
        return None
    m, w = got
    peak = shapes.peaks(r["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * shapes_hyb.model_flops(w, w["block"]) \
        / (m["seconds"] * peak)
