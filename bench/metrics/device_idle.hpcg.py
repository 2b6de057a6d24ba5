"""Share (%) of the traced slice with no op on the device (`layers.device_idle`)."""
from layers import device_idle as read  # noqa: F401
