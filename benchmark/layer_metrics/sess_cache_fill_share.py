"""Cache rows the live sessions hold over the pool's rows at the
window's end, in percent (``pio_sess_cache_tokens`` over
``pio_sess_cache_tokens_capacity``; whole blocks)."""
from benchmark.harness import sess_metrics as _s


def read(r):
    w = _s.work(r)
    if w is None or not w.get("cache_capacity"):
        return None
    return 100.0 * w["cache_tokens"] / w["cache_capacity"]
