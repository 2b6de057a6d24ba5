"""Device milliseconds of the engine's prefill programs (``Engine._prefill``)
per 1,000 prompt tokens prefilled in the traced slice."""
import layers


def read(run):
    s = layers.prefill_seconds(run)
    if s is None:
        return None
    return 1e3 * s / (sum(run.layer["prefill_lengths"]) / 1e3)
