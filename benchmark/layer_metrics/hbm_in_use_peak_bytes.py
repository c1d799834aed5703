"""``peak_bytes_in_use`` of the fullest chip: arrays, so the state the
deployment holds resident (tables, store, bitmap) plus results. Does
not count the programs' scratch: ``hbm_reserved_bytes``."""


def read(r):
    return (r.get("memory") or {}).get("in_use_peak")
