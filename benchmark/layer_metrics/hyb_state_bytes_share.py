"""Bytes the live sessions' SLOTS hold (``pio_sess_state_slots`` x
``pio_sess_state_slot_bytes``) of all the pool holds for them (the
slots and the key and value rows held in the same layers), at the
window's end, in percent."""
from benchmark.harness import hyb_metrics as _h
from benchmark.harness import shapes_hyb


def read(r):
    w = _h.window(r)
    if w is None or not w.get("state_slots"):
        return None
    b = w["block"]
    slots = w["state_slots"] * w["slot_bytes"]
    rows = w["kind_tokens_attn"] * b["n_layers"] \
        * shapes_hyb.cache_row_bytes(b)
    return 100.0 * slots / (slots + rows)
