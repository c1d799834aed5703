"""Share of its roofline the attention over the paged key/value caches
reaches, in percent: every cached row of the group's sessions read once
an attention layer, scored and weighted by the query's rows and 16 query
heads of 256 (``shapes_lin.cache_attention``), over the device time of
the Pallas kernel under ``lin/attn``
(``attention.paged_gqa_attention``)."""
from benchmark.harness import lin_metrics as _l
from benchmark.harness import shapes_lin


def read(r):
    got = _l.sliced(r)
    if got is None:
        return None
    m, w = got
    seconds = _l.under(m["kernels"], "lin/attn")
    need = shapes_lin.cache_attention(w, w["block"])
    return _l.roofline(r, need["flops"], need["bytes"], seconds)
