"""``reading.decode_roofline`` in qwen3-chat, where it moves ``tpot_p95_ms``."""
from harness.reading import decode_roofline as read  # noqa: F401
