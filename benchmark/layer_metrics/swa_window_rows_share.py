"""Cache rows the two block tables hold over the rows ONE table for
every layer would hold, at the window's end, in percent
(``pio_sess_cache_kind_tokens``: the global kind's rows in 2 layers and
the window kind's in 6, over the global kind's in all 8)."""
from benchmark.harness import shapes_swa
from benchmark.harness import swa_metrics as _s


def read(r):
    w = _s.window(r)
    if w is None or not w.get("kind_tokens_global"):
        return None
    return 100.0 * shapes_swa.held_rows(w, w["block"]) \
        / shapes_swa.one_table_rows(w, w["block"])
