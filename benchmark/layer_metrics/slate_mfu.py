"""Share of the chip's bf16 peak the whole round reaches, in percent:
the traced rounds' model FLOPs (``shapes_slate.model_flops``: every
generated token row of every query row's passes through projections,
router, 8 experts, head, and attention over the cache) over the round
program's device time. A memory-bound step: read beside
``slate_hbm_roofline``."""
from benchmark.harness import shapes, shapes_slate
from benchmark.harness import slate_metrics as _s


def read(r):
    got = _s.sliced(r)
    if got is None:
        return None
    m, w = got
    peak = shapes.peaks(r["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * shapes_slate.model_flops(w, w["block"]) \
        / (m["seconds"] * peak)
