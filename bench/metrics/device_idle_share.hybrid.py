"""``reading.idle_share`` in granite-batch, where it moves ``output_tok_s``."""
from harness.reading import idle_share as read  # noqa: F401
