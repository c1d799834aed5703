"""A bfloat16 factor store: float32 -> nearest-even bfloat16, as the
store's cast does, read back as float32."""

from benchmark.harness.oracle import bf16_round as round_table  # noqa: F401

BYTES_PER_ELEMENT = 2
