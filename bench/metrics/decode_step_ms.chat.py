"""Device milliseconds per decode step (`layers.decode_step_ms`)."""
from layers import decode_step_ms as read  # noqa: F401
