"""Share of the round program's device time under ``sdar/moe``
(router, dispatch plan, grouped matmuls over the picked experts,
combine), in percent."""
from benchmark.harness import slate_metrics as _s


def read(r):
    got = _s.sliced(r)
    if got is None:
        return None
    whole = sum(got[0]["scopes"].values())
    return 100.0 * _s.under(got[0]["scopes"], "sdar/moe") / whole \
        if whole else None
