"""Share (%) of the HBM roofline reached by one ``repro.core.spmv`` call.

Bytes are the least traffic of the matrix's SpMV (``work.spmv_min_bytes``:
nonzeros, row pointers, x and y once), counted from the matrix and not
from the format.  The time is all device time of the traced iterations
outside the benchmark's own CG programs, i.e. every program the call
launched (plan gather, kernel, epilogue), over the number of calls.
"""
import peaks


def read(run):
    t, lay = run.trace, run.layer
    if t is None or not lay.get("spmv_calls_traced"):
        return None
    own = set(lay["own_programs"])
    seconds = t.module_seconds(lambda name: name not in own)
    if seconds <= 0:
        return None
    per_call = seconds / lay["spmv_calls_traced"]
    return peaks.hbm_roofline_pct(lay["spmv_bytes"], per_call,
                                  lay["device_kind"])
