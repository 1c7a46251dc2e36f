"""Served requests' share of the chip's peak: useful FLOPs of the requests
completed over (the sum of their latencies x peak FLOP/s)."""


def read(rec):
    if not rec.get("useful_flops_done") or not rec.get("latency_sum_done_s"):
        return None
    return 100.0 * rec["useful_flops_done"] / (
        rec["latency_sum_done_s"] * rec["peak_flops_per_s"])
