"""Share of the device's busy time spent in operations that are not
convolutions (pads, flips, slices, concatenations, activations)."""


def read(rec):
    t = rec.get("trace")
    if not t or not t["busy_s"]:
        return None
    return 100.0 * t["nonconv_s"] / t["busy_s"]
