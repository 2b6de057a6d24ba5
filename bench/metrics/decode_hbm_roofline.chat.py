"""Decode steps' share (%) of the HBM roofline (`layers.decode_hbm_roofline`)."""
from layers import decode_hbm_roofline as read  # noqa: F401
