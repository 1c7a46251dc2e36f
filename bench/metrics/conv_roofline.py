"""Share of the conv work's roofline: the least time of the useful conv
work completed in the traced window (work.py, peaks.json) over the device
time of the conv operations in the trace (trace_reduce.py)."""


def read(rec):
    t = rec.get("trace")
    if not t or not t["conv_s"] or not rec.get("least_s"):
        return None
    return 100.0 * rec["least_s"] / t["conv_s"]
