"""Share of the extend program's device self time under ``hyb/ssd`` (a
layer's Mamba-2 heads: projection, convolution, the scan over the
slot's state, gated norm and output projection), in percent."""
from benchmark.harness import hyb_metrics as _h


def read(r):
    return _h.scope_share(r, "hyb/ssd")
