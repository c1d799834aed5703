"""Share of the extend program's device self time under ``lin/gdn``
(a Gated DeltaNet layer's projections, convolution, rule, gated norm and
output projection, and the slot's read and write), in percent."""
from benchmark.harness import lin_metrics as _l


def read(r):
    return _l.scope_share(r, "lin/gdn")
