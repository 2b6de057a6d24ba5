"""The whole serving step's model FLOP utilization (%) (`layers.serve_mfu`)."""
from layers import serve_mfu as read  # noqa: F401
