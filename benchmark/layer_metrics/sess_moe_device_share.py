"""Share of the extend program's device self time under ``sess/moe``
(the dense feed-forward of the leading layer, router, dispatch, the
grouped matmuls over the held experts, combine, the shared expert), in
percent."""
from benchmark.harness import sess_metrics as _s


def read(r):
    return _s.scope_share(r, "sess/moe")
