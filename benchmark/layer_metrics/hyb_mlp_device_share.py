"""Share of the extend program's device self time under ``hyb/mlp``
(the dense SwiGLU of 21,504: 77% of a layer's weights, streamed whole
every dispatch), in percent."""
from benchmark.harness import hyb_metrics as _h


def read(r):
    return _h.scope_share(r, "hyb/mlp")
