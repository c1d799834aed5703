"""Bytes the live sessions' SLOTS hold (``pio_sess_state_slots`` x
``pio_sess_state_slot_bytes``) of all the pool holds for them (the
slots and the attention kind's held key and value rows), at the
window's end, in percent."""
from benchmark.harness import lin_metrics as _l
from benchmark.harness import shapes_lin


def read(r):
    w = _l.window(r)
    if w is None or not w.get("state_slots"):
        return None
    b = w["block"]
    slots = w["state_slots"] * w["slot_bytes"]
    rows = w["kind_tokens_full"] * shapes_lin.n_full(b) \
        * shapes_lin.cache_row_bytes(b)
    return 100.0 * slots / (slots + rows)
