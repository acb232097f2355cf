"""``reading.decode_iter_ms`` in qwen3-chat, where it moves ``tpot_p95_ms``."""
from harness.reading import decode_iter_ms as read  # noqa: F401
