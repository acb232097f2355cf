"""``reading.decode_roofline`` in granite-batch, where it moves
``output_tok_s``: the hybrid family's ``decode_work`` (bf16 weights once
per iteration, live KV, and every Mamba layer's whole state read and
written per lane-token) over the fused decode program's device time."""
from harness.reading import decode_roofline as read  # noqa: F401
