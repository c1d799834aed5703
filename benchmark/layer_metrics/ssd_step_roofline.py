"""Share of its roofline the Mamba-2 mixers' step reaches, in percent:
the bytes (the mixer's weights a dispatch and layer, every live query's
slot in and out) and operations (projections, convolution, the scan's
passes over the state) of ``shapes_hyb.ssd_step`` against the device
time under the ``hyb/ssd`` scope, whatever implements it, PLUS these
mixers' share of the time under NO scope: XLA streams the fixed weights
ahead of the matmuls that use them by asynchronous copies that carry no
scope (PERF.md section 5, cell 9; ``gdn_step_roofline`` reckons the
same), so the scope's own time leaves out the stream of its own
weights; the share is the mixers' weights over all the weights so
streamed (``shapes_hyb.weights_prefetched``)."""
from benchmark.harness import hyb_metrics as _h
from benchmark.harness import shapes_hyb


def read(r):
    got = _h.sliced(r)
    if got is None:
        return None
    m, w = got
    b = w["block"]
    need = shapes_hyb.ssd_step(w, b)
    mine = b["n_layers"] * shapes_hyb.ssm_weights(b) \
        / shapes_hyb.weights_prefetched(b)
    return _h.roofline(r, need["flops"], need["bytes"],
                       _h.under(m["scopes"], "hyb/ssd")
                       + mine * m["scopes"].get("", 0.0))
