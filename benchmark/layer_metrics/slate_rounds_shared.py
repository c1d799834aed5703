"""Query rows a round dispatch decoded, mean over the window
(``pio_slate_rounds_total`` over the lane's round dispatches): what
carrying unfinished queries to their next round beside new arrivals
buys."""
from benchmark.harness import slate_metrics as _s


def read(r):
    w = _s.window(r)
    return None if w is None or not w["dispatches"] \
        else w["rounds"] / float(w["dispatches"])
