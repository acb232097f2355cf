"""``reading.decode_roofline`` in danube-batch, where it moves ``output_tok_s``."""
from harness.reading import decode_roofline as read  # noqa: F401
