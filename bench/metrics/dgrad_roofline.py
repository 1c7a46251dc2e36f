"""Share of the dgrad work's roofline: the least time of the useful input-
gradient work completed in the traced window (work.py, peaks.json) over
the device time of the dgrad conv kernels (trace_scopes.py)."""


def read(rec):
    if not rec.get("dgrad_conv_s") or not rec.get("dgrad_least_s"):
        return None
    return 100.0 * rec["dgrad_least_s"] / rec["dgrad_conv_s"]
