"""``reading.idle_share`` in qwen3-chat, where it moves ``tpot_p95_ms``."""
from harness.reading import idle_share as read  # noqa: F401
