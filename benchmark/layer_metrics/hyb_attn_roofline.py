"""Share of its roofline the attention over the paged key/value caches
reaches, in percent: every cached row of the group's sessions read once
a layer, scored and weighted by the query's rows and 20 query heads of
128, 5 a key/value head (``shapes_hyb.cache_attention``), over the
device time of the Pallas kernel under ``hyb/attn``
(``attention.paged_gqa_attention``)."""
from benchmark.harness import hyb_metrics as _h
from benchmark.harness import shapes_hyb


def read(r):
    got = _h.sliced(r)
    if got is None:
        return None
    m, w = got
    seconds = _h.under(m["kernels"], "hyb/attn")
    need = shapes_hyb.cache_attention(w, w["block"])
    return _h.roofline(r, need["flops"], need["bytes"], seconds)
