"""``bytes_reserved`` of the fullest chip after the window: the pool
the runtime sets aside for the loaded programs' temporaries (XLA
scratch), which ``bytes_in_use`` does not count. A program that copies
a table before it reads it shows here, not in
``hbm_in_use_peak_bytes``."""


def read(r):
    return (r.get("memory") or {}).get("reserved")
