"""Share of its roofline the attention over the paged key/value caches
reaches, in percent: every VISIBLE row of the group's sessions (a
window layer: the newest 4,095 and the query's own) read once a layer,
scored and weighted by the query's rows and 28 query heads
(``shapes_swa.cache_attention``), over the device time of the Pallas
kernel under ``swa/attn`` (``attention.paged_gqa_attention``)."""
from benchmark.harness import shapes_swa
from benchmark.harness import swa_metrics as _s


def read(r):
    got = _s.sliced(r)
    if got is None:
        return None
    m, w = got
    seconds = _s.under(m["kernels"], "swa/attn")
    need = shapes_swa.cache_attention(w, w["block"])
    return _s.roofline(r, need["flops"], need["bytes"], seconds)
