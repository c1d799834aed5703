"""Forward passes a query's slate took, mean over the window: its
rows' denoising passes and commit passes
(``pio_slate_passes_total{kind="query"}``) over the queries answered."""
from benchmark.harness import slate_metrics as _s


def read(r):
    w = _s.window(r)
    return None if w is None or not w["queries"] \
        else w["passes_query"] / float(w["queries"])
