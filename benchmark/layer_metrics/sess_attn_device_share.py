"""Share of the extend program's device self time under the indexer's
and the attention's scopes (``sess/index``, ``sess/select``,
``sess/attend``), in percent."""
from benchmark.harness import sess_metrics as _s


def read(r):
    return _s.scope_share(r, "sess/index", "sess/select", "sess/attend")
