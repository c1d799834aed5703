"""Share of its roofline the Gated DeltaNet layers' step reaches, in
percent: the bytes (the mixer's weights a dispatch and layer, every
live query's slot in and out) and operations (projections, convolution,
the rule's passes over the state) of ``shapes_lin.gdn_step`` against
the device time under the ``lin/gdn`` scope, whatever implements it,
PLUS these layers' share of the time under NO scope: XLA streams the
fixed weights ahead of the matmuls that use them by asynchronous copies
that carry no scope (PERF.md section 5, cell 9), so the scope's own
time leaves out the stream of its 405 MB of weights; the share is the
mixers' weights over all the weights so streamed
(``shapes_lin.weights_prefetched``)."""
from benchmark.harness import lin_metrics as _l
from benchmark.harness import shapes_lin


def read(r):
    got = _l.sliced(r)
    if got is None:
        return None
    m, w = got
    b = w["block"]
    need = shapes_lin.gdn_step(w, b)
    mine = shapes_lin.n_gdn(b) * shapes_lin.gdn_weights(b) \
        / shapes_lin.weights_prefetched(b)
    return _l.roofline(r, need["flops"], need["bytes"],
                       _l.under(m["scopes"], "lin/gdn")
                       + mine * m["scopes"].get("", 0.0))
