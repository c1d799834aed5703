"""An int8 factor store: symmetric per-row absmax quantization (scale =
absmax / 127, round half to even, all-zero rows keep scale 1), read
back as float32. The rule ``ops/quantize.py`` states, written out here
so the oracle does not move with the program."""

import numpy as np

BYTES_PER_ELEMENT = 1


def round_table(a: np.ndarray) -> np.ndarray:
    a = np.asarray(a, dtype=np.float32)
    absmax = np.abs(a).max(axis=1, keepdims=True)
    scale = np.where(absmax > 0, absmax / 127.0, 1.0).astype(np.float32)
    return (np.clip(np.round(a / scale), -127, 127) * scale) \
        .astype(np.float32)
