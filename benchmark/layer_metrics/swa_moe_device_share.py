"""Share of the extend program's device self time under ``swa/moe``
(router, dispatch, the grouped matmuls over the picked ReGLU experts,
combine), in percent."""
from benchmark.harness import swa_metrics as _s


def read(r):
    got = _s.sliced(r)
    if got is None:
        return None
    whole = sum(got[0]["scopes"].values())
    return 100.0 * _s.under(got[0]["scopes"], "swa/moe") / whole \
        if whole else None
