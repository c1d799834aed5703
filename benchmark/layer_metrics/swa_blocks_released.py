"""Cache blocks the window kind gave back inside the measured window
(``pio_sess_window_blocks_released_total``: a block a session every 256
events it grows past the model's window), on the dispatcher's thread."""
from benchmark.harness import swa_metrics as _s


def read(r):
    w = _s.window(r)
    return None if w is None else w.get("blocks_released")
