"""Share of its roofline the attention over the paged key/value cache
reaches, in percent: every cached row of the group's sessions read
once a pass and layer, scored and weighted by the block's rows and 32
query heads (``shapes_slate.cache_attention``), over the device time
of the Pallas kernel under ``sdar/attn``
(``attention.paged_gqa_attention``)."""
from benchmark.harness import shapes_slate
from benchmark.harness import slate_metrics as _s


def read(r):
    got = _s.sliced(r)
    if got is None:
        return None
    m, w = got
    seconds = _s.under(m["kernels"], "sdar/attn")
    need = shapes_slate.cache_attention(w, w["block"])
    return _s.roofline(r, need["flops"], need["bytes"], seconds)
