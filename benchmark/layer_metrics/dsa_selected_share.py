"""Keys the indexer kept over the keys eligible (cached positions up
to a token's own), over the window's new events and every layer, in
percent: ``pio_sess_keys_total`` selected over eligible."""
from benchmark.harness import sess_metrics as _s


def read(r):
    w = _s.work(r)
    if w is None or not w.get("eligible"):
        return None
    return 100.0 * w["selected"] / w["eligible"]
