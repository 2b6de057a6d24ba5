"""Median milliseconds a request waited from when it was due until the
engine slotted it (``Request.queue_s``, the engine's own field), over the
requests slotted inside the traced slice."""
import numpy as np


def read(run):
    q = run.layer.get("queue_s")
    return None if not q else 1e3 * float(np.median(q))
