"""Share (%) of the device's busy time that the SpMV calls take in CG:
device time outside the benchmark's own CG programs over all device time
in the traced window."""


def read(run):
    t, lay = run.trace, run.layer
    if t is None or t.busy_s <= 0:
        return None
    own = set(lay["own_programs"])
    return 100.0 * t.module_seconds(lambda n: n not in own) \
        / t.module_seconds(lambda n: True)
