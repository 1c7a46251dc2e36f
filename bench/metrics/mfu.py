"""Whole pass's share of the chip's peak: useful FLOPs completed in the
traced window over (window x chips x peak FLOP/s)."""


def read(rec):
    if not rec.get("useful_flops") or "passes" not in rec:
        return None
    return 100.0 * rec["useful_flops"] / (
        rec["window_s"] * rec["chips"] * rec["peak_flops_per_s"])
