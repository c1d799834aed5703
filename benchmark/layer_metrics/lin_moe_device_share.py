"""Share of the extend program's device self time under ``lin/moe``
(router, dispatch, the grouped matmuls over the picked experts of the
128 held, combine, the gated shared expert), in percent."""
from benchmark.harness import lin_metrics as _l


def read(r):
    return _l.scope_share(r, "lin/moe")
