"""``reading.decode_iter_ms`` in granite-batch, where it moves ``output_tok_s``."""
from harness.reading import decode_iter_ms as read  # noqa: F401
