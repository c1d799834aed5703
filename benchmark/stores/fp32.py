"""A float32 factor store: the tables as the trainer hands them over."""

import numpy as np

BYTES_PER_ELEMENT = 4


def round_table(a: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(a, dtype=np.float32)
